"""Pure-jnp oracles for the fused FrODO update kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import memory as fmem


def frodo_update_ref(g: jax.Array, hist: jax.Array, cursor: jax.Array,
                     weights: jax.Array, alpha: float, beta: float):
    """Exact-memory fused update.
    g: (...,), hist: (T, ...), cursor: scalar int, weights: (T,) mu.
    Returns (delta, new_hist)."""
    M = fmem.exact_memory_term(hist, cursor, weights)
    delta = -(alpha * g + beta * M.astype(g.dtype))
    new_hist = fmem.exact_push(hist, cursor, g)
    return delta, new_hist


def frodo_expsum_apply_ref(g: jax.Array, acc: jax.Array, p: jax.Array,
                           scale, rates, coeffs, alpha: float, beta: float):
    """Exp-sum update and apply in float32, each output rounded once.
    acc: (K, ...); rates, coeffs: (K,).  Returns (new_acc, new_p)."""
    f32 = jnp.float32
    g = jnp.asarray(scale, f32) * g.astype(f32)
    a = acc.astype(f32)
    M = jnp.tensordot(jnp.asarray(coeffs, f32), a, axes=(0, 0),
                      precision="highest")
    r = jnp.asarray(rates, f32).reshape((-1,) + (1,) * g.ndim)
    new_p = p.astype(f32) - (alpha * g + beta * M)
    return (r * (a + g[None])).astype(acc.dtype), new_p.astype(p.dtype)
