"""Pallas TPU kernels for the fused FrODO parameter update.

The update is memory-bound: the exact mode streams a (T x n) gradient
history once per step; the exp-sum mode streams (K x n) accumulators and
writes them back.  Fusing the weighted reduction with the axpy update makes
each HBM byte count once — unfused jnp does
  read hist (Tn) -> write M (n) -> read M,g,x -> write x      (T n + 3n reads)
while the kernels do a single pass with the M accumulator resident in VMEM.

Layout: callers (ops.py) flatten the parameter to 2-D (R, 128) tiles; the
grid walks row-blocks; each program holds a (T|K, BR, 128) history tile and
a (BR, 128) accumulator in VMEM.  BR is chosen so the working set stays
under ~4 MiB of the 16 MiB VMEM (double-buffered by the pipeline: ~8 MiB).
BR is a multiple of 16 rows, which meets both the f32 (8, 128) and the bf16
(16, 128) tiling, or the whole of R when R is smaller; the last block may
be ragged.  The per-slot weights, rates and coefficients are scalars read
by index, so they live in SMEM.

Off the TPU a kernel runs only in Pallas' interpret mode, and only when the
caller asks for it around the call (tests use
``jax.experimental.pallas.tpu.force_tpu_interpret_mode``); without that
request Pallas refuses to lower the kernel for another backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 16                 # row multiple that tiles both f32 and bf16
VMEM_BUDGET = 4 * 2 ** 20    # bytes of one pipeline buffer set
MAX_BR = 512


def _pick_br(R: int, slots: int, itemsize: int) -> int:
    """Rows per program: keep (slots + 2) * BR * LANE * itemsize under the
    VMEM budget with BR a multiple of SUBLANE, or take all of R if fewer."""
    br = VMEM_BUDGET // ((slots + 2) * LANE * itemsize)
    br = min(MAX_BR, max(SUBLANE, br // SUBLANE * SUBLANE))
    return R if R <= br else br


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


# ------------------------------------------------------------------ exact

def _exact_kernel(w_ref, g_ref, hist_ref, delta_ref, *, T, alpha, beta):
    g = g_ref[...]                                   # (BR, LANE)
    acc = jnp.zeros(g.shape, jnp.float32)

    def body(t, acc):
        return acc + w_ref[t] * hist_ref[t].astype(jnp.float32)

    M = jax.lax.fori_loop(0, T, body, acc)
    delta_ref[...] = (-(alpha * g.astype(jnp.float32) + beta * M)
                      ).astype(delta_ref.dtype)


def exact_update_2d(g2: jax.Array, hist2: jax.Array, w_slot: jax.Array,
                    alpha: float, beta: float) -> jax.Array:
    """g2: (R, LANE); hist2: (T, R, LANE); w_slot: (T,) slot-rotated weights.
    Returns delta (R, LANE).  (History push is a cheap XLA dynamic-update
    done by the caller — rewriting all T slots would defeat the point.)"""
    T, R, _ = hist2.shape
    br = _pick_br(R, T, hist2.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_exact_kernel, T=T, alpha=alpha, beta=beta),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            _SMEM,
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((T, br, LANE), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANE), g2.dtype),
        name="frodo_exact_update",
    )(w_slot.astype(jnp.float32), g2, hist2)


# ----------------------------------------------------------------- expsum

def _expsum_kernel(r_ref, c_ref, g_ref, acc_ref, delta_ref, newacc_ref,
                   *, K, alpha, beta):
    g = g_ref[...].astype(jnp.float32)               # (BR, LANE)
    M = jnp.zeros(g.shape, jnp.float32)
    for k in range(K):                               # K is small (~8): unroll
        a = acc_ref[k].astype(jnp.float32)
        M = M + c_ref[k] * a
        newacc_ref[k] = (r_ref[k] * (a + g)).astype(newacc_ref.dtype)
    delta_ref[...] = (-(alpha * g + beta * M)).astype(delta_ref.dtype)


def expsum_update_2d(g2: jax.Array, acc2: jax.Array, rates: jax.Array,
                     coeffs: jax.Array, alpha: float, beta: float):
    """g2: (R, LANE); acc2: (K, R, LANE).  Returns (delta, new_acc); the new
    accumulators are written over ``acc2``'s buffer, block by block."""
    K, R, _ = acc2.shape
    br = _pick_br(R, 2 * K, acc2.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_expsum_kernel, K=K, alpha=alpha, beta=beta),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            _SMEM,
            _SMEM,
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((K, br, LANE), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANE), lambda i: (i, 0)),
            pl.BlockSpec((K, br, LANE), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, LANE), g2.dtype),
            jax.ShapeDtypeStruct(acc2.shape, acc2.dtype),
        ],
        input_output_aliases={3: 1},
        name="frodo_expsum_update",
    )(rates.astype(jnp.float32), coeffs.astype(jnp.float32), g2, acc2)
