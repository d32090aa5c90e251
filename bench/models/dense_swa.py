"""The dense sliding-window transformer of h2o-danube-1.8b (arXiv:2401.16818)
as the benchmark sees it: its parameter tree, its FLOP count, the check of
the program's model, and its plain float32 forward pass and loss, layer by
layer, for ``harness/reference.py``.

A configuration names this module with ``"model_code": "dense_swa"``.  The
model follows the published description with the program's conventions
where the paper leaves a choice: RoPE rotates interleaved pairs, GQA query
head h reads key/value head h // (H / G), RMSNorm scales after normalising.
It imports nothing of the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness.flops import attention_keys_per_token
from harness.reference import mm

NEG = -1e30


def leaf_shapes(m: dict) -> dict:
    """``{path: (shape, fan_in)}`` of one agent's parameters; ``fan_in`` 0
    marks a norm scale, -1 the embedding table."""
    d, H, G, f, V, L = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_ff"], m["vocab"], m["n_layers"])
    hd = m.get("head_dim") or d // H
    shapes = {
        "embed/table": ((V, d), -1),
        "ln_f/scale": ((d,), 0),
        "blocks/ln1/scale": ((L, d), 0),
        "blocks/ln2/scale": ((L, d), 0),
        "blocks/attn/wq/w": ((L, d, H, hd), d),
        "blocks/attn/wk/w": ((L, d, G, hd), d),
        "blocks/attn/wv/w": ((L, d, G, hd), d),
        "blocks/attn/wo/w": ((L, H, hd, d), H * hd),
        "blocks/mlp/up/w": ((L, d, f), d),
        "blocks/mlp/down/w": ((L, f, d), f),
    }
    if m["gated_mlp"]:
        shapes["blocks/mlp/gate/w"] = ((L, d, f), d)
    if not m["tie_embeddings"]:
        shapes["lm_head/w"] = ((d, V), d)
    return shapes


def stacks(m: dict) -> dict:
    """The stacked layer groups: ``{prefix: number of layers}``."""
    return {"blocks": m["n_layers"]}


# ------------------------------------------------------------- FLOPs

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


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) FLOPs per token, no recompute:
    6 N_active plus causal (and windowed) attention."""
    H = m["n_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    attn_fwd = (m["n_layers"] * 2.0 * H * (hd + hd)
                * attention_keys_per_token(seq_len, m["window"]))
    return 6.0 * matmul_params(m) + 3.0 * attn_fwd


# ------------------------------------------------- the program's model

def check_model(cfg, m: dict) -> None:
    """The program's model must be the configuration file's."""
    have = {"family": cfg.family, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd(),
            "d_ff": cfg.d_ff, "vocab": cfg.vocab, "window": cfg.window,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "activation": cfg.activation, "gated_mlp": cfg.gated_mlp,
            "tie_embeddings": cfg.tie_embeddings,
            "param_dtype": cfg.param_dtype,
            "compute_dtype": cfg.compute_dtype}
    diff = {k: (v, m[k]) for k, v in have.items() if v != m[k]}
    if cfg.attn_type != "swa" or cfg.rope_fraction != 1.0 or cfg.qk_norm \
            or cfg.logit_softcap:
        diff["attention"] = (cfg.attn_type, "swa with full RoPE")
    if diff:
        raise ValueError(f"the program's model differs from the "
                         f"configuration file: {diff}")


# ------------------------------------------------ the float32 reference

def rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, heads, hd); rotates interleaved pairs (0,1), (2,3), ..."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def block(bp, x, m, quant=None):
    """One decoder layer: pre-norm GQA attention (causal, sliding window)
    and a gated SiLU MLP, each added to the residual stream."""
    H, G = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    S = x.shape[1]
    h = rmsnorm(bp["ln1/scale"], x, m["norm_eps"])
    q = rope(mm("bsd,dhk->bshk", h, bp["attn/wq/w"], quant), m["rope_theta"])
    k = rope(mm("bsd,dgk->bsgk", h, bp["attn/wk/w"], quant), m["rope_theta"])
    v = mm("bsd,dgk->bsgk", h, bp["attn/wv/w"], quant)
    k = jnp.repeat(k, H // G, axis=2)
    v = jnp.repeat(v, H // G, axis=2)
    s = mm("bshk,bthk->bhst", q, k, quant) / np.sqrt(hd)
    dist = np.arange(S)[:, None] - np.arange(S)[None, :]
    ok = dist >= 0
    if m["window"] > 0:
        ok &= dist < m["window"]
    s = jnp.where(jnp.asarray(ok), s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhst,bthk->bshk", p, v, quant)
    x = x + mm("bshk,hkd->bsd", o, bp["attn/wo/w"], quant)
    h = rmsnorm(bp["ln2/scale"], x, m["norm_eps"])
    up = mm("bsd,df->bsf", h, bp["mlp/up/w"], quant)
    if m["gated_mlp"]:
        up = jax.nn.silu(mm("bsd,df->bsf", h, bp["mlp/gate/w"], quant)) * up
    else:
        up = jax.nn.silu(up)
    return x + mm("bsf,fd->bsd", up, bp["mlp/down/w"], quant)


def head_loss(ln_f, w, x, labels, m, quant=None, half=False):
    """Token-mean cross entropy of the final norm and the LM head."""
    h = rmsnorm(ln_f, x, m["norm_eps"])
    logits = mm("bsd,dv->bsv", h, w, quant)
    ce = (jax.nn.logsumexp(logits, -1)
          - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
    if half:
        ce = ce[:, : ce.shape[1] // 2]
    return jnp.mean(ce)


@partial(jax.jit, static_argnames=("m", "quant"))
def _block_fwd(bp, x, m, quant):
    return block(bp, x, dict(m), quant)


@partial(jax.jit, static_argnames=("m", "quant"))
def _block_bwd(bp, x, dy, m, quant):
    _, vjp = jax.vjp(lambda p, z: block(p, z, dict(m), quant), bp, x)
    return vjp(dy)


@partial(jax.jit, static_argnames=("m", "quant", "half"))
def _head_grad(ln_f, w, x, labels, m, quant, half):
    return jax.value_and_grad(
        lambda a, b, z: head_loss(a, b, z, labels, dict(m), quant, half),
        argnums=(0, 1, 2))(ln_f, w, x)


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@jax.jit
def _embed_grad(table, tokens, dx):
    return jnp.zeros_like(table).at[tokens.reshape(-1)].add(
        dx.reshape(-1, dx.shape[-1]))


def agent_grad(p: dict, tokens, labels, m: dict, quant=None, half=False):
    """(loss, grads) of one agent, layer by layer; ``p`` is flat f32 with
    each layer's leaves apart (``reference.split_layers``)."""
    mk = tuple(sorted(m.items()))
    L = m["n_layers"]
    layer = [{k[len(f"blocks.{i}/"):]: v for k, v in p.items()
              if k.startswith(f"blocks.{i}/")} for i in range(L)]
    xs = [_embed(p["embed/table"], tokens)]
    for bp in layer:
        xs.append(_block_fwd(bp, xs[-1], mk, quant))
    w = p["embed/table"].T if m["tie_embeddings"] else p["lm_head/w"]
    loss, (d_lnf, d_w, dx) = _head_grad(p["ln_f/scale"], w, xs[-1], labels,
                                        mk, quant, half)
    grads = {"ln_f/scale": d_lnf}
    for i in reversed(range(L)):
        d, dx = _block_bwd(layer[i], xs[i], dx, mk, quant)
        grads.update({f"blocks.{i}/{k}": v for k, v in d.items()})
        xs[i + 1] = None
    d_table = _embed_grad(p["embed/table"], tokens, dx)
    if m["tie_embeddings"]:
        d_table = d_table + d_w.T
    else:
        grads["lm_head/w"] = d_w
    grads["embed/table"] = d_table
    return loss, grads
