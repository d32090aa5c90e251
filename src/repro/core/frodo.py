"""FrODO optimizer (Algorithm 1, stage 1+2) as an optax-style transform.

The consensus stage (stage 3) is deliberately factored out into
``core.consensus`` — in the distributed trainer it is a collective over the
agent mesh axes, not part of the per-agent optimizer.  This file implements
the per-agent update

    g_i   = grad f_i(x_i)
    M_i   = sum_{n=1..T} mu(n; lambda) g_i^(k-n)
    x_i  <- x_i - alpha g_i - beta M_i

with two memory representations (exact circular buffer / exponential-sum
accumulators, see core.memory) and fused Pallas kernels
(kernels/frodo_update.py): the exact mode's behind ``use_kernel``; the
exp-sum mode's whenever it runs on a TPU, through ``Optimizer.apply``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import memory as fmem
from repro.kernels import frodo_update as kfu
from repro.obs import metrics as obs_metrics
from repro.obs.timing import trace_scope

Params = Any
Grads = Any
State = Any

#: scalar aux metrics attached to the optimizer state when
#: ``FrodoConfig.collect_metrics`` is set (see docs/observability.md)
METRIC_NAMES = ("grad_norm", "memory_norm", "update_norm")


class Optimizer(NamedTuple):
    """Optax-style pair.  ``update`` returns (delta, new_state); the caller
    applies ``params = params + delta``.

    ``apply``, where the optimizer has one, is the update and its
    application in one call: ``apply(grads, state, params, scale, mesh,
    specs)`` returns (new params, new state) as ``update`` on
    ``scale * grads`` and ``apply_updates`` would (``scale`` None is 1;
    ``mesh`` and ``specs``, the params' PartitionSpecs, where the step runs
    on a mesh)."""
    init: Callable[[Params], State]
    update: Callable[[Grads, State, Optional[Params]], tuple[Any, State]]
    apply: Optional[Callable[..., tuple[Params, State]]] = None


@dataclasses.dataclass(frozen=True)
class FrodoConfig:
    alpha: float = 0.8          # gradient term magnitude
    beta: float = 0.35          # memory feedback magnitude
    lam: float = 0.15           # fractional order exponent, in (0,1)
    T: int = 90                 # memory length
    memory_mode: str = "exact"  # "exact" (paper) | "expsum" (beyond-paper)
    K: int = 8                  # number of exponentials for expsum mode
    exponent_scale: float = 1.0
    # exact mode: route the update arithmetic through the Pallas kernel.  The
    # exp-sum mode ignores it: on a TPU its ``apply`` is always the fused
    # kernel (kernels/frodo_update.py:expsum_apply), elsewhere always jnp
    use_kernel: bool = False
    acc_dtype: str = "float32"  # expsum accumulator dtype (bf16 halves state)
    pad_T: int = 0              # buffer size override (weights zero beyond T)
    collect_metrics: bool = False  # aux ||g||/||M||/||delta|| in state["metrics"]

    def __post_init__(self):
        if self.memory_mode not in ("exact", "expsum"):
            raise ValueError(f"bad memory_mode {self.memory_mode!r}")
        if not (0.0 < self.lam < 1.0):
            raise ValueError("lambda must be in (0,1) per Algorithm 1")


def frodo(cfg: FrodoConfig) -> Optimizer:
    if cfg.memory_mode == "exact":
        return _frodo_exact(cfg)
    return _frodo_expsum(cfg)


# ------------------------------------------------------------------ exact

def _frodo_exact(cfg: FrodoConfig) -> Optimizer:
    T_buf = max(cfg.pad_T, cfg.T)
    w = np.zeros(T_buf)
    w[:cfg.T] = fmem.mu_weights(cfg.T, cfg.lam, cfg.exponent_scale)
    weights = jnp.asarray(w, dtype=jnp.float32)

    def init(params: Params) -> State:
        hist = jax.tree.map(lambda p: fmem.exact_init(p, T_buf), params)
        state = {"step": jnp.zeros((), jnp.int32), "hist": hist}
        if cfg.collect_metrics:
            state["metrics"] = obs_metrics.zeros_like_metrics(METRIC_NAMES)
        return state

    def update(grads: Grads, state: State, params: Optional[Params] = None):
        cursor = jnp.mod(state["step"], T_buf)
        collect = cfg.collect_metrics
        if cfg.use_kernel:
            from repro.kernels import ops as kops
            def leaf(g, h):
                newx_delta, newh = kops.frodo_update(
                    g, h, cursor, weights, cfg.alpha, cfg.beta)
                # the kernel fuses M into the axpy; recompute it only when
                # telemetry asks for ||M||
                M = (fmem.exact_memory_term(h, cursor, weights)
                     if collect else None)
                return newx_delta, newh, M
        else:
            def leaf(g, h):
                M = fmem.exact_memory_term(h, cursor, weights)
                delta = -(cfg.alpha * g + cfg.beta * M.astype(g.dtype))
                return delta, fmem.exact_push(h, cursor, g), \
                    (M if collect else None)
        flat_g, treedef = jax.tree.flatten(grads)
        flat_h = treedef.flatten_up_to(state["hist"])
        out = [leaf(g, h) for g, h in zip(flat_g, flat_h)]
        delta = treedef.unflatten([o[0] for o in out])
        hist = treedef.unflatten([o[1] for o in out])
        new_state = {"step": state["step"] + 1, "hist": hist}
        if collect:
            Ms = treedef.unflatten([o[2] for o in out])
            new_state["metrics"] = obs_metrics.frodo_step_metrics(
                grads, Ms, delta)
        return delta, new_state

    return Optimizer(init, update)


# ---------------------------------------------------------------- expsum

def _frodo_expsum(cfg: FrodoConfig) -> Optimizer:
    rates_np, coeffs_np = fmem.fit_expsum(cfg.T, cfg.lam, cfg.K,
                                          cfg.exponent_scale)
    rates = jnp.asarray(rates_np, jnp.float32)
    coeffs = jnp.asarray(coeffs_np, jnp.float32)

    def init(params: Params) -> State:
        adt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg.acc_dtype]
        acc = jax.tree.map(
            lambda p: fmem.expsum_init(p, cfg.K).astype(adt), params)
        state = {"step": jnp.zeros((), jnp.int32), "acc": acc}
        if cfg.collect_metrics:
            state["metrics"] = obs_metrics.zeros_like_metrics(METRIC_NAMES)
        return state

    def leaf(g, a):
        M = fmem.expsum_memory_term(a, coeffs)
        delta = -(cfg.alpha * g + cfg.beta * M.astype(g.dtype))
        return delta, fmem.expsum_push(a, rates, g), M

    def update(grads: Grads, state: State, params: Optional[Params] = None):
        flat_g, treedef = jax.tree.flatten(grads)
        flat_a = treedef.flatten_up_to(state["acc"])
        out = [leaf(g, a) for g, a in zip(flat_g, flat_a)]
        delta = treedef.unflatten([o[0] for o in out])
        acc = treedef.unflatten([o[1] for o in out])
        new_state = {"step": state["step"] + 1, "acc": acc}
        if cfg.collect_metrics:
            Ms = treedef.unflatten([o[2] for o in out])
            new_state["metrics"] = obs_metrics.frodo_step_metrics(
                grads, Ms, delta)
        return delta, new_state

    def leaf_jnp(g, a, p, scale):
        if scale is not None:
            g = (g * scale).astype(g.dtype)
        delta, a, _ = leaf(g, a)
        return a, p + delta.astype(p.dtype)

    fused = functools.partial(
        kfu.expsum_apply, rates=tuple(map(float, rates_np)),
        coeffs=tuple(map(float, coeffs_np)), alpha=cfg.alpha, beta=cfg.beta)
    logged = []

    def apply(grads: Grads, state: State, params: Params, scale=None,
              mesh=None, specs=None):
        """One call for update and apply.  On a TPU (or under Pallas' TPU
        interpret mode) every leaf that the kernel tiles in its device
        layout is one ``expsum_apply`` pass, per shard under a mesh; the
        rest, and every leaf elsewhere, take the jnp update."""
        if cfg.collect_metrics:
            raise ValueError("apply keeps no metrics: use update")
        device = (mesh.devices.flat[0] if mesh is not None
                  else kfu.default_device())
        kernel = device.platform == "tpu" or kfu.interpret_forced()
        flat_g, treedef = jax.tree.flatten(grads)
        flat_a = treedef.flatten_up_to(state["acc"])
        flat_p = treedef.flatten_up_to(params)
        flat_s = (treedef.flatten_up_to(specs) if mesh is not None
                  else [None] * len(flat_g))
        orders = [kfu.expsum_order(
            p.shape if sp is None else jax.sharding.NamedSharding(
                mesh, sp).shard_shape(p.shape),
            p.dtype, a.dtype, cfg.K, device) if kernel else None
            for p, a, sp in zip(flat_p, flat_a, flat_s)]
        if not logged:
            logged.append(True)
            _log_split(flat_p, orders, device)
        s = jnp.float32(1) if scale is None else scale
        out = []
        for g, a, p, sp, order in zip(flat_g, flat_a, flat_p, flat_s,
                                      orders):
            if order is None:
                out.append(leaf_jnp(g, a, p, scale))
                continue
            call = functools.partial(fused, order=order)
            if mesh is not None:
                P = jax.sharding.PartitionSpec
                acc_spec = P(None, *sp)
                call = jax.shard_map(call, mesh=mesh,
                                     in_specs=(sp, acc_spec, sp, P()),
                                     out_specs=(acc_spec, sp),
                                     check_vma=False)
            with trace_scope("pallas.frodo_expsum_apply"):
                out.append(call(g, a, p, s))
        acc = treedef.unflatten([o[0] for o in out])
        new_params = treedef.unflatten([o[1] for o in out])
        return new_params, {"step": state["step"] + 1, "acc": acc}

    return Optimizer(init, update, apply)


def _log_split(flat_p, orders, device) -> None:
    """One record each for the parameters on the fused path and on jnp."""
    for name, on in (("frodo.fused_params", True),
                     ("frodo.jnp_params", False)):
        sizes = [int(np.prod(p.shape)) for p, o in zip(flat_p, orders)
                 if (o is not None) == on]
        obs_metrics.record(name, sum(sizes), leaves=len(sizes),
                           platform=device.platform)


# ------------------------------------------------------------------ helpers

def apply_updates(params: Params, delta: Any) -> Params:
    return jax.tree.map(lambda p, d: (p + d.astype(p.dtype)), params, delta)


def memory_bytes(params: Params, cfg: FrodoConfig) -> int:
    """Thm 2.2 accounting: O(Tn) exact / O(Kn) expsum state, in bytes."""
    n = sum(int(np.prod(p.shape)) * p.dtype.itemsize
            for p in jax.tree.leaves(params))
    mult = cfg.T if cfg.memory_mode == "exact" else cfg.K
    return mult * n
