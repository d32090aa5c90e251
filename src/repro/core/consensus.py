"""Stage-3 consensus: x <- W x over the agent dimension, as JAX collectives.

Two execution styles, matching the two ways the trainer can be lowered:

* **stacked** — agent states carry an explicit leading dim A (sharded over the
  agent mesh axes under jit).  Mixing is an einsum with the row-stochastic W;
  XLA lowers it to all-gather/all-reduce over the agent axes.  Special cases
  avoid the O(A n) gather:
    - ``uniform complete`` W == 11^T/A  -> mean over axis 0 (all-reduce, O(n));
    - ``hierarchical``  W = W_pod (x) W_intra with optional period H on the
      cross-pod factor (cross-pod traffic rides DCN; mixing it every H steps
      is the beyond-paper DiLoCo-flavored schedule).

* **mapped** — inside shard_map, each device holds its agent's slice; mixing
  uses lax collectives by axis name (pmean / ppermute ring).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs.timing import trace_scope

Pytree = Any


def is_uniform_complete(W: np.ndarray, tol: float = 1e-9) -> bool:
    A = W.shape[0]
    return bool(np.allclose(W, np.full((A, A), 1.0 / A), atol=tol))


# ------------------------------------------------------------------ stacked

def mix_stacked(x: Pytree, W, with_metrics: bool = False):
    """x[a] <- sum_b W[a,b] x[b]   for every leaf (leading dim = agents).

    ``W`` is either a host numpy matrix (the static healthy-graph path, with
    the uniform-complete all-reduce shortcut) or a traced jax array — e.g.
    one step of a fault-masked ``W_seq`` — which always takes the general
    einsum (no data-dependent shortcuts under tracing).

    ``with_metrics=True`` additionally returns the aux scalar pytree
    ``{"consensus_error_pre", "consensus_error_post"}`` — the RMS per-agent
    disagreement before/after mixing (the Thm 2.1 Lyapunov quantity).  The
    default single-return path is byte-identical to a metrics-free build.
    """
    A = W.shape[0]
    if isinstance(W, np.ndarray) and is_uniform_complete(W):
        with trace_scope("consensus.mix_uniform"):
            out = jax.tree.map(
                lambda v: jnp.broadcast_to(jnp.mean(v, axis=0, keepdims=True),
                                           v.shape).astype(v.dtype), x)
    else:
        Wj = jnp.asarray(W, jnp.float32)

        def leaf(v):
            o = jnp.einsum("ab,b...->a...", Wj, v.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
            return o.astype(v.dtype)

        with trace_scope("consensus.mix_general"):
            out = jax.tree.map(leaf, x)
    if not with_metrics:
        return out
    aux = {"consensus_error_pre": obs_metrics.consensus_error(x),
           "consensus_error_post": obs_metrics.consensus_error(out)}
    return out, aux


def mix_time_varying(x: Pytree, W_seq, step, with_metrics: bool = False):
    """Fault-aware consensus: apply step ``step``'s matrix of a precompiled
    (K, A, A) mixing sequence (``faults.CompiledFaults.W_seq``) to the
    stacked states.  ``W_seq`` is baked into the jitted program as a
    constant; ``step`` may be traced (a scan counter) — indexing selects the
    round's masked, renormalized W_t.  Steps beyond the schedule horizon
    wrap around (``step % K``), so a K-step schedule describes a repeating
    fault pattern for longer runs."""
    Wj = jnp.asarray(W_seq, jnp.float32)
    W_t = Wj[jnp.mod(step, Wj.shape[0])]
    with trace_scope("consensus.mix_time_varying"):
        return mix_stacked(x, W_t, with_metrics=with_metrics)


def mix_hierarchical(x: Pytree, W_intra: np.ndarray, W_pod: np.ndarray,
                     step: jax.Array, period: int = 1) -> Pytree:
    """Two-level mixing on a leading dim A = P*D (pod-major).

    Intra-pod factor applied every step; cross-pod factor applied when
    ``step % period == 0``.  period=1 recovers W_pod (x) W_intra exactly.
    """
    P, D = W_pod.shape[0], W_intra.shape[0]

    def leaf(v):
        tail = v.shape[1:]
        u = v.reshape((P, D) + tail).astype(jnp.float32)
        if is_uniform_complete(W_intra):
            u = jnp.broadcast_to(jnp.mean(u, axis=1, keepdims=True), u.shape)
        else:
            u = jnp.einsum("de,pe...->pd...", jnp.asarray(W_intra, jnp.float32), u)

        def cross(u):
            if is_uniform_complete(W_pod):
                return jnp.broadcast_to(jnp.mean(u, axis=0, keepdims=True),
                                        u.shape)
            return jnp.einsum("qp,pd...->qd...", jnp.asarray(W_pod, jnp.float32), u)

        if period > 1:
            u = jax.lax.cond(jnp.mod(step, period) == 0, cross, lambda z: z, u)
        else:
            u = cross(u)
        return u.reshape(v.shape).astype(v.dtype)

    with trace_scope("consensus.mix_hierarchical"):
        return jax.tree.map(leaf, x)


def mix_uniform_constrained(tree: Pytree, specs: Pytree, mesh) -> Pytree:
    """Uniform complete-graph consensus with explicit sharding constraints:
    sum over the agent-sharded dim (lowers to an all-reduce among devices
    sharing the model coords), constrain the mean to the agent-free spec,
    then broadcast back to the stacked layout (no traffic).  This pins the
    2x-local-bytes lowering; the unconstrained mean+broadcast lets the SPMD
    partitioner pick an agent-dim all-gather (A x bytes) instead."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def leaf(v, sp):
        A = v.shape[0]
        rest = tuple(sp)[1:] if len(tuple(sp)) else ()
        m = jnp.sum(v.astype(jnp.float32), axis=0) / A
        m = jax.lax.with_sharding_constraint(
            m, NamedSharding(mesh, P(*rest)))
        out = jnp.broadcast_to(m[None], v.shape).astype(v.dtype)
        return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, sp))

    with trace_scope("consensus.mix_uniform_constrained"):
        return jax.tree.map(leaf, tree, specs, is_leaf=lambda x: False)


def pmean_shardmap(tree: Pytree, agent_axes, mesh) -> Pytree:
    """Uniform complete-graph consensus lowered explicitly as an all-reduce
    over the agent mesh axes (shard_map manual over ONLY those axes; model/
    fsdp axes stay compiler-managed).  The naive stacked mean+broadcast
    lowers to an agent-dim all-gather (A x param bytes per device); pmean
    moves 2 x local bytes — the difference is ~A/2."""
    axes = tuple(agent_axes)
    spec = jax.sharding.PartitionSpec(axes if len(axes) > 1 else axes[0])
    specs = jax.tree.map(lambda _: spec, tree)

    def f(t):
        return jax.tree.map(lambda v: jax.lax.pmean(v, axes), t)

    with trace_scope("consensus.pmean_shardmap"):
        return jax.shard_map(f, mesh=mesh, in_specs=(specs,),
                             out_specs=specs, axis_names=set(axes))(tree)


# ------------------------------------------------------------------- mapped
# For use INSIDE shard_map(..., axis_names including the agent axes).

def pmean_mix(x: Pytree, axis_names: Sequence[str]) -> Pytree:
    """Uniform complete-graph consensus: all-reduce mean over agent axes."""
    def leaf(v):
        out = v
        for ax in axis_names:
            out = jax.lax.pmean(out, ax)
        return out.astype(v.dtype)
    with trace_scope("consensus.pmean_mix"):
        return jax.tree.map(leaf, x)


def ring_mix(x: Pytree, axis_name: str, w_self: float = 0.5,
             bidirectional: bool = True) -> Pytree:
    """Ring consensus via collective_permute — O(n) per device per neighbor,
    no all-gather.  w_self + neighbor weights sum to 1 (row-stochastic)."""
    n_nbrs = 2 if bidirectional else 1
    w_nbr = (1.0 - w_self) / n_nbrs
    size = jax.lax.axis_size(axis_name)

    def leaf(v):
        fwd = jax.lax.ppermute(
            v, axis_name, [(i, (i + 1) % size) for i in range(size)])
        acc = w_self * v.astype(jnp.float32) + w_nbr * fwd.astype(jnp.float32)
        if bidirectional:
            bwd = jax.lax.ppermute(
                v, axis_name, [(i, (i - 1) % size) for i in range(size)])
            acc = acc + w_nbr * bwd.astype(jnp.float32)
        return acc.astype(v.dtype)

    with trace_scope("consensus.ring_mix"):
        return jax.tree.map(leaf, x)


def general_mix(x: Pytree, W: np.ndarray, axis_name: str) -> Pytree:
    """Arbitrary row-stochastic W inside shard_map: all-gather then contract.
    O(A n) per device — the fallback for arbitrary digraphs."""
    Wj = jnp.asarray(W, jnp.float32)

    def leaf(v):
        allv = jax.lax.all_gather(v, axis_name)            # (A, ...)
        idx = jax.lax.axis_index(axis_name)
        out = jnp.tensordot(Wj[idx], allv.astype(jnp.float32), axes=(0, 0))
        return out.astype(v.dtype)

    with trace_scope("consensus.general_mix"):
        return jax.tree.map(leaf, x)
