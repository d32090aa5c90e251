"""Pallas TPU kernels for the fused FrODO parameter update.

The update is memory-bound: the exact mode streams a (T x n) gradient
history once per step; the exp-sum mode streams (K x n) accumulators and
writes them back.  Fusing the weighted reduction with the axpy update makes
each HBM byte count once.

Exact mode (``exact_update_2d``, behind ``ops.frodo_update`` and
``FrodoConfig.use_kernel``): callers flatten the parameter to 2-D
(R, 128) tiles; the grid walks row-blocks; each program holds a
(T, BR, 128) history tile and a (BR, 128) accumulator in VMEM.  BR is
chosen so the working set stays under ~4 MiB of the 16 MiB VMEM
(double-buffered by the pipeline: ~8 MiB), a multiple of 16 rows, which
meets both the f32 (8, 128) and the bf16 (16, 128) tiling, or the whole of
R when R is smaller; the last block may be ragged.  The per-slot weights
are scalars read by index, so they live in SMEM.

Exp-sum mode (``expsum_apply``, the exp-sum optimizer's update on the
TPU): one pass reads g, the K accumulators and the parameter once and
writes the accumulators and the parameter in place, 22 bytes a parameter
with bf16 state and K = 4.  It walks each leaf in its own layout: the
device's default layout of the leaf (``expsum_order``) is put in row-major
order by a transpose that XLA lowers to a bitcast, and blocks tile the
two minor dims, so no operand is copied on the way in or out.  Leaves
whose minor dims do not tile (norm scales, biases) stay on jnp.

Off the TPU a kernel runs only in Pallas' interpret mode, and only when the
caller asks for it around the call (tests use
``jax.experimental.pallas.tpu.force_tpu_interpret_mode``); without that
request Pallas refuses to lower the kernel for another backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
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

BLOCK_BYTES = 12 * 2 ** 20   # HBM bytes a block of the exp-sum pass moves
MAX_COLS = 4096              # widest column block of a wider 128-lane leaf
APPLY_VMEM_BYTES = 64 * 2 ** 20   # two blocks' buffers and the f32 values


def _divisor_at_most(n: int, cap: int, step: int = 1) -> int:
    """Largest multiple of ``step`` that divides ``n`` and is at most
    ``cap``, or 0."""
    return next((d for d in range(cap - cap % step, 0, -step) if n % d == 0),
                0)


def param_bytes(K: int, dtype, acc_dtype) -> int:
    """HBM bytes the exp-sum pass moves per parameter: g, the K
    accumulators and p in; the accumulators and p out."""
    item = jnp.dtype(dtype).itemsize
    return 3 * item + 2 * K * jnp.dtype(acc_dtype).itemsize


def expsum_block(shape, bytes_per_param: int) -> tuple | None:
    """The block of a leaf of this shape over its two minor dims, or None
    where they do not tile: fewer than 2 dims or fewer than SUBLANE rows
    (norm scales, biases), or rows that no multiple of SUBLANE divides
    where one block cannot take them all.

    A block moves at most BLOCK_BYTES (``bytes_per_param`` a parameter),
    and every block divides the leaf.  Columns: the whole minor dim, or
    its largest 128-lane divisor up to MAX_COLS where it is wider.  Rows:
    the largest multiple of SUBLANE that divides them and keeps the block
    within its size.  Where the two minor dims fit in one block, a block
    takes whole matrices, as many as divide the dim before them."""
    if len(shape) < 2 or shape[-2] < SUBLANE:
        return None
    most = max(1, BLOCK_BYTES // bytes_per_param)
    R, C = shape[-2:]
    bc = C
    if C > MAX_COLS and C % LANE == 0:
        bc = _divisor_at_most(C, MAX_COLS, LANE)
    if bc == C and R * C <= most:
        if len(shape) == 2:
            return (R, C)
        return (_divisor_at_most(shape[-3], most // (R * C)), R, C)
    br = _divisor_at_most(R, max(SUBLANE, most // bc), SUBLANE)
    return (br, bc) if br else None


def interpret_forced() -> bool:
    """True inside ``pltpu.force_tpu_interpret_mode``: the kernels then run,
    interpreted, on any backend."""
    from jax._src import config
    mode = getattr(config, "pallas_tpu_interpret_mode_context_manager", None)
    return mode is not None and mode.value is not None


def default_device():
    """The device that a computation without placement runs on."""
    dev = jax.config.jax_default_device
    if isinstance(dev, str):
        return jax.devices(dev)[0]
    return dev if dev is not None else jax.devices()[0]


def _major_to_minor(shape, dtype, device) -> tuple:
    layout = device.client.get_default_layout(jnp.dtype(dtype), tuple(shape),
                                              device)
    return Layout.from_pjrt_layout(layout).major_to_minor


def expsum_order(shape, dtype, acc_dtype, K: int, device) -> tuple | None:
    """The major-to-minor order of a leaf's dims in ``device``'s default
    layout, where ``expsum_apply`` takes the leaf (of ``dtype``, with K
    accumulators of ``acc_dtype``): the accumulators' default layout is
    the leaf's behind a major K dim, and the leaf, so ordered, tiles
    (``expsum_block``).  None keeps the leaf on jnp."""
    order = _major_to_minor(shape, dtype, device)
    acc_order = _major_to_minor((K,) + tuple(shape), acc_dtype, device)
    if acc_order != (0,) + tuple(d + 1 for d in order):
        return None
    if expsum_block(tuple(shape[d] for d in order),
                    param_bytes(K, dtype, acc_dtype)) is None:
        return None
    return order


def _apply_kernel(s_ref, g_ref, acc_ref, p_ref, acc_out, p_out, *, rates,
                  coeffs, alpha, beta):
    g = s_ref[0] * g_ref[...].astype(jnp.float32)
    M = jnp.zeros(g.shape, jnp.float32)
    for k, (r, c) in enumerate(zip(rates, coeffs)):   # K is small: unroll
        a = acc_ref[k].astype(jnp.float32)
        M = M + c * a
        acc_out[k] = (r * (a + g)).astype(acc_out.dtype)
    p_out[...] = (p_ref[...].astype(jnp.float32)
                  - (alpha * g + beta * M)).astype(p_out.dtype)


def expsum_apply(g: jax.Array, acc: jax.Array, p: jax.Array,
                 scale: jax.Array, *, rates: tuple, coeffs: tuple,
                 alpha: float, beta: float, order: tuple | None = None):
    """One HBM pass of the exp-sum FrODO update and its apply for one leaf
    whose shape ``expsum_block`` tiles.

    g, p: the leaf's shape; acc: (K,) + that shape; scale: () f32, the
    clip scale; rates, coeffs: K Python floats.  In float32, g' = scale g,
    M = sum_k c_k S_k (old accumulators), S_k <- r_k (S_k + g'),
    p <- p - (alpha g' + beta M); each output is rounded once to its
    dtype.  Returns (new acc, new p), written over ``acc``'s and ``p``'s
    buffers.

    ``order`` (``expsum_order``) puts the leaf's dims major to minor as
    the device lays them out; the kernel runs on that transposed view,
    which matches the HBM bytes, and transposes its results back.  The
    major dims and the blocks of the two minor ones are the grid; there is
    no reshape of any operand."""
    order = tuple(range(p.ndim)) if order is None else tuple(order)
    acc_order = (0,) + tuple(d + 1 for d in order)
    g, p = jnp.transpose(g, order), jnp.transpose(p, order)
    acc = jnp.transpose(acc, acc_order)
    block = expsum_block(p.shape, param_bytes(len(rates), p.dtype,
                                               acc.dtype))
    lead = p.ndim - len(block)
    grid = p.shape[:lead] + tuple(pl.cdiv(n, b) for n, b in
                                  zip(p.shape[lead:], block))
    spec = pl.BlockSpec((pl.squeezed,) * lead + block, lambda *i: i)
    acc_spec = pl.BlockSpec((len(rates),) + spec.block_shape,
                            lambda *i: (0,) + i)
    new_acc, new_p = pl.pallas_call(
        functools.partial(_apply_kernel, rates=tuple(map(float, rates)),
                          coeffs=tuple(map(float, coeffs)),
                          alpha=float(alpha), beta=float(beta)),
        grid=grid,
        in_specs=[_SMEM, spec, acc_spec, spec],
        out_specs=[acc_spec, spec],
        out_shape=[jax.ShapeDtypeStruct(acc.shape, acc.dtype),
                   jax.ShapeDtypeStruct(p.shape, p.dtype)],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=APPLY_VMEM_BYTES),
        name="frodo_expsum_apply",
    )(jnp.reshape(scale, (1,)).astype(jnp.float32), g, acc, p)
    return (jnp.transpose(new_acc, np.argsort(acc_order)),
            jnp.transpose(new_p, np.argsort(order)))
