"""Model FLOPs per trained token, from the configuration file's sizes.

The arithmetic of the program's ``utils/flops.py:train_flops`` (6 N_active
per token, the LM head in and the embedding gather out), copied here so the
yardstick cannot move with the program, with two changes: attention is
counted as the causal (and windowed) work it needs, and no recomputed
operation counts (remat is the program's choice, not work the model needs).
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matmul or an elementwise product per
    token: every leaf but the embedding table (a gather)."""
    d, H, G = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    attn = d * H * hd + 2 * d * G * hd + H * hd * d
    mlp = (3 if m["gated_mlp"] else 2) * d * m["d_ff"]
    layer = attn + mlp + 2 * d                       # + two norm scales
    head = 0 if m["tie_embeddings"] else d * m["vocab"]
    return m["n_layers"] * layer + head + d          # + final norm


def attention_keys_per_token(seq_len: int, window: int) -> float:
    """Mean number of keys a query attends to under a causal mask and a
    sliding window (0: none)."""
    w = window if window > 0 else seq_len
    total = sum(min(i + 1, w) for i in range(seq_len))
    return total / seq_len


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) FLOPs per token, no recompute."""
    H = m["n_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    attn_fwd = (m["n_layers"] * 2.0 * H * (hd + hd)
                * attention_keys_per_token(seq_len, m["window"]))
    return 6.0 * matmul_params(m) + 3.0 * attn_fwd
