"""Losses and metrics."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 0.0) -> Tuple[jax.Array, Dict]:
    """Token-mean CE.  logits (..., V) any float dtype; labels (...) int32,
    negative labels are masked out."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    ce = lse - gold
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (ce * mask).sum() / denom
    out = loss
    if z_loss > 0:
        out = out + z_loss * ((lse ** 2) * mask).sum() / denom
    acc = ((lf.argmax(-1) == labels) * mask).sum() / denom
    return out, {"ce": loss, "accuracy": acc}


def chunked_cross_entropy(x, head_w, labels, n_chunks: int = 8,
                          softcap: float = 0.0):
    """CE over (B,S,d) features without materializing (B,S,V) fp32 logits:
    rows are processed in checkpointed chunks, so the backward recomputes
    each chunk's logits instead of keeping them live (the fused-CE pattern).

    x: (B,S,d); head_w: (d,V); labels: (B,S) int32 (negatives masked).
    Returns (loss, metrics) like ``cross_entropy``."""
    B, S, d = x.shape
    N = B * S
    while N % n_chunks:
        n_chunks //= 2
    n_chunks = max(n_chunks, 1)
    xr = x.reshape(n_chunks, N // n_chunks, d)
    lr = labels.reshape(n_chunks, N // n_chunks)

    @jax.checkpoint
    def chunk(xc, lc):
        logits = jnp.einsum("nd,dv->nv", xc, head_w).astype(jnp.float32)
        if softcap > 0:
            logits = softcap * jnp.tanh(logits / softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        correct = ((logits.argmax(-1) == lc) * mask).sum()
        return ((lse - gold) * mask).sum(), mask.sum(), correct

    def body(acc, args):
        ce, m, corr = chunk(*args)
        return (acc[0] + ce, acc[1] + m, acc[2] + corr), None

    (ce_sum, mask_sum, corr), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (xr, lr))
    denom = jnp.maximum(mask_sum, 1.0)
    loss = ce_sum / denom
    return loss, {"ce": loss, "accuracy": corr / denom}


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_scale(norm: jax.Array, max_norm: float) -> jax.Array:
    """The factor that brings a tree of global norm ``norm`` to at most
    ``max_norm``."""
    return jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = clip_scale(norm, max_norm)
    return jax.tree.map(lambda x: (x * scale).astype(x.dtype), tree), norm
