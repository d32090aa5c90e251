"""jit'd wrapper of the exact-mode kernel: arbitrary-shape params -> 2-D
tiles -> Pallas kernel.  (The exp-sum kernel, ``frodo_update.expsum_apply``,
takes each leaf in its own layout and needs no wrapper.)"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import memory as fmem
from repro.kernels import frodo_update as K
from repro.obs.timing import trace_scope

LANE = K.LANE


def _to_2d(x: jax.Array):
    """Flatten to (R, LANE), zero-padded.  Returns (x2, n)."""
    n = int(np.prod(x.shape)) if x.ndim else 1
    R = max(1, -(-n // LANE))
    pad = R * LANE - n
    flat = x.reshape(-1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return flat.reshape(R, LANE), n


def _from_2d(x2: jax.Array, shape, n: int):
    return x2.reshape(-1)[:n].reshape(shape)


@partial(jax.jit, static_argnames=("alpha", "beta"))
def frodo_update(g: jax.Array, hist: jax.Array, cursor: jax.Array,
                 weights: jax.Array, alpha: float, beta: float):
    """Fused exact-memory FrODO update for one param leaf.
    g: (...); hist: (T, ...); weights: (T,) mu.  Returns (delta, new_hist)."""
    T = hist.shape[0]
    # rotate mu onto buffer slots: slot s holds g^(k-n), n = (cursor-s) mod T
    s = jnp.arange(T)
    nn = jnp.mod(cursor - s, T)
    nn = jnp.where(nn == 0, T, nn)
    w_slot = weights[nn - 1]
    with trace_scope("pallas.frodo_exact_update"):
        g2, n = _to_2d(g)
        h2 = jax.vmap(lambda h: _to_2d(h)[0])(hist)
        delta2 = K.exact_update_2d(g2, h2, w_slot, alpha, beta)
        delta = _from_2d(delta2, g.shape, n)
        new_hist = fmem.exact_push(hist, cursor, g)
    return delta, new_hist
