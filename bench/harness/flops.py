"""FLOP arithmetic that every model's count shares.

A model's ``train_flops_per_token`` (``bench/models/<model_code>.py``)
follows the program's ``utils/flops.py:train_flops`` (6 N_active per
token, the LM head in and the embedding gather out), copied so that the
yardstick cannot move with the program, with two changes: attention is
counted as the causal (and windowed) work it needs, and no recomputed
operation counts (remat is the program's choice, not work the model needs).
"""
from __future__ import annotations


def attention_keys_per_token(seq_len: int, window: int) -> float:
    """Mean number of keys a query attends to under a causal mask and a
    sliding window (0: none)."""
    w = window if window > 0 else seq_len
    total = sum(min(i + 1, w) for i in range(seq_len))
    return total / seq_len
