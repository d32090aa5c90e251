"""The distributed FrODO training step.

Layout: every param leaf carries a leading **agent** dim A (sharded over the
agent mesh axes).  Per-agent forward/backward runs under ``vmap`` over that
dim; the per-agent FrODO update is elementwise so it maps transparently; the
consensus stage mixes the agent dim with the configured W / hierarchical
schedule.  A=1 degenerates to ordinary (FSDP x TP) data-parallel training
with centralized fractional-order GD — the paper's N=1 corner.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import consensus as C
from repro.core import graph as G
from repro.core.faults import FaultSchedule
from repro.core.frodo import FrodoConfig, Optimizer, apply_updates, frodo
from repro.core import baselines
from repro.distributed import sharding as SH
from repro.models import transformer as T
from repro.obs import metrics as obs_metrics
from repro.obs.timing import trace_scope
from repro.training.loss import (cross_entropy, chunked_cross_entropy,
                                 clip_by_global_norm, clip_scale,
                                 global_norm)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    ce_chunks: int = 8                   # chunked-CE row chunks (memory)
    optimizer: str = "frodo"             # frodo|no_memory|heavy_ball|nesterov|adam
    alpha: float = 0.02                  # gradient step (LR)
    beta: float = 0.008                  # memory feedback
    lam: float = 0.15
    T: int = 90
    memory_mode: str = "expsum"          # expsum default at LLM scale
    K: int = 8
    acc_dtype: str = "float32"
    use_kernel: bool = False             # exact mode only (FrodoConfig)
    grad_clip: float = 1.0
    remat: object = True        # False | True("nothing") | "dots" | "dots_no_batch"
    microbatches: int = 1                # grad-accumulation steps per round
    # consensus
    topology: str = "complete"           # complete|ring|hierarchical
    weights: str = "xiao_boyd"           # uniform|metropolis|xiao_boyd
    consensus_interval: int = 1          # mix every H steps (beyond-paper)
    cross_pod_period: int = 1            # hierarchical: DCN mixing period
    # fault injection (core/faults.py): a schedule compiles to per-step
    # masked mixing matrices + agent update masks, baked as constants over
    # ``fault_horizon`` steps and cycled (step % horizon) beyond it
    fault_schedule: Optional[FaultSchedule] = None
    fault_horizon: int = 64
    # observability: emit consensus_error/memory_norm/... as extra scalar
    # outputs of train_step (drained to a sink by the trainer).  Static flag:
    # False lowers to a jaxpr byte-identical to a metrics-free build.
    collect_metrics: bool = False


class TrainState(NamedTuple):
    params: Any          # (A, ...) stacked
    opt_state: Any
    step: jax.Array


def build_optimizer(tc: TrainConfig) -> Optimizer:
    if tc.optimizer == "frodo":
        return frodo(FrodoConfig(alpha=tc.alpha, beta=tc.beta, lam=tc.lam,
                                 T=tc.T, memory_mode=tc.memory_mode, K=tc.K,
                                 use_kernel=tc.use_kernel,
                                 acc_dtype=tc.acc_dtype,
                                 collect_metrics=tc.collect_metrics))
    if tc.optimizer == "no_memory":
        return baselines.no_memory(tc.alpha)
    if tc.optimizer == "heavy_ball":
        return baselines.heavy_ball(tc.alpha, tc.beta)
    if tc.optimizer == "nesterov":
        return baselines.nesterov(tc.alpha)
    if tc.optimizer == "adam":
        return baselines.adam(tc.alpha)
    raise ValueError(tc.optimizer)


def build_mixing(tc: TrainConfig, n_agents: int, n_pods: int = 1):
    """Returns (W, W_intra, W_pod) — W for flat mixing, the pair for
    hierarchical."""
    if n_agents == 1:
        return np.ones((1, 1)), None, None
    if tc.topology == "hierarchical" and n_pods > 1:
        intra = n_agents // n_pods
        W_intra = _weights(tc.weights, G.complete(intra))
        W_pod = _weights(tc.weights, G.complete(n_pods))
        return None, W_intra, W_pod
    topo = {"complete": G.complete, "ring": partial(G.ring, directed=False)}[
        tc.topology](n_agents)
    return _weights(tc.weights, topo), None, None


def _weights(kind: str, A: np.ndarray) -> np.ndarray:
    return {"uniform": G.uniform_weights, "metropolis": G.metropolis_weights,
            "xiao_boyd": G.xiao_boyd_weights}[kind](A)


# ------------------------------------------------------------------ rules

def build_rules(cfg: ModelConfig, multi_pod: bool) -> Dict[str, Any]:
    agent_axes = cfg.agent_axes_multi if multi_pod else cfg.agent_axes_single
    all_data = ("pod", "data") if multi_pod else ("data",)
    leftover = tuple(a for a in all_data if a not in agent_axes)
    rules = dict(SH.DEFAULT_RULES)
    rules["agent"] = tuple(agent_axes) or None
    rules["batch"] = leftover or None
    rules["fsdp"] = leftover if (cfg.fsdp and leftover) else None
    return rules


def serve_rules(cfg: ModelConfig, multi_pod: bool, batch: int,
                mesh, weights_fsdp: bool = False) -> Dict[str, Any]:
    """Serving has no agents: batch over the data axes when divisible, else
    the KV sequence dim takes them (flash-decode style cache split).

    ``weights_fsdp`` additionally shards weights over the data axes
    (gathered per layer at use) — required to fit models whose TP-sharded
    weights alone exceed HBM (kimi-k2 1T on a 256-chip pod)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    all_data = ("pod", "data") if multi_pod else ("data",)
    total = int(np.prod([sizes[a] for a in all_data]))
    rules = dict(SH.DEFAULT_RULES)
    rules["agent"] = None
    if batch % total == 0 and batch >= total:
        rules["batch"] = all_data
        rules["kv_seq"] = "model"       # split long caches across TP shards
    else:
        rules["batch"] = None
        rules["kv_seq"] = all_data + ("model",)
    rules["fsdp"] = all_data if weights_fsdp else None
    return rules


def n_agents_for(cfg: ModelConfig, mesh, multi_pod: bool) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = cfg.agent_axes_multi if multi_pod else cfg.agent_axes_single
    return int(np.prod([sizes[a] for a in axes])) if axes else 1


# ------------------------------------------------------------- spec trees

def sanitize_specs(specs: Any, shapes: Any, mesh) -> Any:
    """Drop mesh axes from dims they don't divide."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(spec, leaf):
        parts = list(tuple(spec) + (None,) * (len(leaf.shape) - len(tuple(spec))))
        out = []
        for dim, p in zip(leaf.shape, parts):
            if p is None:
                out.append(None)
                continue
            axes = p if isinstance(p, tuple) else (p,)
            prod = int(np.prod([sizes[a] for a in axes]))
            out.append(p if (prod and dim % prod == 0) else None)
        return jax.sharding.PartitionSpec(*out)

    return jax.tree.map(one, specs, shapes,
                        is_leaf=lambda x: isinstance(
                            x, jax.sharding.PartitionSpec))


def param_specs(param_shapes: Any, rules: Dict[str, Any], mesh,
                agent_stacked: bool = True) -> Any:
    specs = SH.spec_tree(param_shapes, rules, agent_stacked=agent_stacked)
    return sanitize_specs(specs, param_shapes, mesh)


def opt_state_specs(opt_shapes: Any, p_specs: Any, param_shapes: Any,
                    mesh) -> Any:
    """Derive optimizer-state specs from param specs: leaves whose shape is
    (X,) + param_shape get (None,) + param_spec; same-shape leaves inherit."""
    flat_p = SH._flatten_with_paths(param_shapes)
    flat_ps = SH._flatten_with_paths(p_specs)

    def match(path: str, leaf):
        # path like "hist/<param path>" or "m/<param path>" or "step"
        parts = path.split("/", 1)
        if len(parts) == 2 and parts[1] in flat_p:
            pshape = flat_p[parts[1]].shape
            pspec = flat_ps[parts[1]]
            if tuple(leaf.shape) == tuple(pshape):
                return pspec
            if tuple(leaf.shape[1:]) == tuple(pshape):
                return jax.sharding.PartitionSpec(*((None,) + tuple(pspec)))
        return jax.sharding.PartitionSpec()

    flat_o = SH._flatten_with_paths(opt_shapes)
    out = {p: match(p, l) for p, l in flat_o.items()}
    specs = SH._unflatten_with_paths(out)
    return specs


_CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "ssm": ("batch", "heads", None, None),
    "conv": ("batch", None, "mlp"),
    "h": ("batch", "mlp"),
    "c_kv": ("batch", "kv_seq", None),
    "k_rope": ("batch", "kv_seq", None),
    "cross_k": ("batch", "frames", "kv_heads", None),
    "cross_v": ("batch", "frames", "kv_heads", None),
}


def cache_specs(cache_shapes: Any, rules: Dict[str, Any], mesh) -> Any:
    """Specs for the decode cache: leaves are matched by their final field
    name (KVCache.k, MambaCache.ssm, ...); every leaf carries a leading
    layer-stack dim."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    specs = []
    for path, leaf in paths:
        key = jax.tree_util.keystr((path[-1],)).strip(".[]'\"")
        axes = _CACHE_AXES.get(key, ())
        axes = (None,) + axes                      # layer-stack dim
        specs.append(SH.logical_to_spec(
            (axes + (None,) * len(leaf.shape))[:len(leaf.shape)], rules))
    specs = jax.tree_util.tree_unflatten(treedef, specs)
    return sanitize_specs(specs, cache_shapes, mesh)


def batch_specs_serve(batch_shapes: Dict[str, Any], rules: Dict[str, Any],
                      mesh) -> Dict[str, Any]:
    """Serving batch: (B, S[, ...]) -> (batch, None, ...)."""
    def one(leaf):
        axes = ("batch",) + (None,) * (len(leaf.shape) - 1)
        return SH.logical_to_spec(axes, rules)
    specs = jax.tree.map(one, batch_shapes)
    return sanitize_specs(specs, batch_shapes, mesh)


# --------------------------------------------------------------- the step

def make_loss_fn(cfg: ModelConfig, tc: TrainConfig):
    from repro.training.loss import chunked_cross_entropy

    def loss_fn(params, batch):
        x, aux = T.forward_features(params, batch, cfg, remat=tc.remat)
        ce, metrics = chunked_cross_entropy(
            x, T.head_weight(params, cfg), batch["labels"],
            n_chunks=tc.ce_chunks, softcap=cfg.logit_softcap)
        return ce + aux, metrics
    return loss_fn


def make_train_step(cfg: ModelConfig, tc: TrainConfig, n_agents: int,
                    n_pods: int = 1) -> Callable:
    """Builds train_step(state, batch) -> (state, metrics).  Batch leaves
    carry the leading agent dim A (= n_agents)."""
    opt = build_optimizer(tc)
    W, W_intra, W_pod = build_mixing(tc, n_agents, n_pods)
    loss_fn = make_loss_fn(cfg, tc)

    faults = None
    if tc.fault_schedule is not None and n_agents > 1:
        if W is None:
            raise ValueError("fault injection does not compose with the "
                             "hierarchical topology (flatten to complete/"
                             "ring, or drop the schedule)")
        adj = {"complete": G.complete,
               "ring": partial(G.ring, directed=False)}[tc.topology](n_agents)
        # reuse the already-built weights so the healthy-step W is identical
        # to the no-fault build
        faults = tc.fault_schedule.compile(adj, tc.fault_horizon,
                                           weight_fn=lambda _A: W)
        fault_counters = {k: jnp.asarray(v)
                          for k, v in faults.counter_arrays().items()}
        fault_u = jnp.asarray(faults.update_mask, jnp.float32)
        fault_W_seq = jnp.asarray(faults.W_seq, jnp.float32)
    # the optimizer's one-call update and apply, where it has one; masked
    # updates (faults) and the optimizer's metrics take update + apply
    fused_apply = (opt.apply is not None and faults is None
                   and not tc.collect_metrics)

    def agent_grad_fn(params1, batch1):
        """Per-agent (loss, metrics), grads — microbatched grad accumulation
        when tc.microbatches > 1 (cuts activation memory ~linearly)."""
        vg = jax.value_and_grad(loss_fn, has_aux=True)
        M = tc.microbatches
        if M <= 1:
            return vg(params1, batch1)
        mb = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch1)

        def step(acc, mbatch):
            (l, met), g = vg(params1, mbatch)
            g_acc, l_acc, m_acc = acc
            g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
            m_acc = jax.tree.map(lambda a, b: a + b, m_acc, met)
            return (g_acc, l_acc + l, m_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params1)
        met0 = {"ce": jnp.float32(0), "accuracy": jnp.float32(0)}
        (g, l, met), _ = jax.lax.scan(step, (g0, jnp.float32(0), met0), mb)
        g = jax.tree.map(lambda x: x / M, g)
        met = jax.tree.map(lambda x: x / M, met)
        return (l / M, met), g

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        grad_fn = agent_grad_fn
        with trace_scope("train.fwd_bwd"):
            if n_agents == 1:
                sq = jax.tree.map(lambda x: x[0], (state.params, batch))
                (loss, metrics), grads = grad_fn(*sq)
                loss = loss[None]
                metrics = jax.tree.map(lambda x: x[None], metrics)
                grads = jax.tree.map(lambda x: x[None], grads)
            else:
                (loss, metrics), grads = jax.vmap(grad_fn)(state.params,
                                                           batch)

        with trace_scope("frodo.update"):
            max_norm = tc.grad_clip * np.sqrt(n_agents)
            if fused_apply:
                # update and apply in one call: the clip's global norm is
                # its own reduction, its scale goes into the update
                if tc.grad_clip > 0:
                    gnorm = global_norm(grads)
                    scale = clip_scale(gnorm, max_norm)
                else:
                    gnorm, scale = jnp.float32(0), None
                mesh = SH.current_mesh()
                specs = None if mesh is None else param_specs(
                    jax.eval_shape(lambda p: p, state.params),
                    SH.current_rules() or {}, mesh, agent_stacked=True)
                params, opt_state = opt.apply(grads, state.opt_state,
                                              state.params, scale,
                                              mesh=mesh, specs=specs)
            else:
                if tc.grad_clip > 0:
                    grads, gnorm = clip_by_global_norm(grads, max_norm)
                else:
                    gnorm = jnp.float32(0)

                if faults is not None:
                    # stragglers / crashed agents: gradient discarded and
                    # update withheld for the step (state moves only via
                    # consensus)
                    u_t = fault_u[jnp.mod(state.step, fault_u.shape[0])]

                    def agent_mask(t):
                        return jax.tree.map(
                            lambda v: v * u_t.reshape(
                                (n_agents,) + (1,) * (v.ndim - 1)
                            ).astype(v.dtype), t)

                    grads = agent_mask(grads)

                delta, opt_state = opt.update(grads, state.opt_state,
                                              state.params)
                if faults is not None:
                    delta = agent_mask(delta)
                params = apply_updates(state.params, delta)
        pre_mix = params

        # stage 3: consensus over the agent dim
        if n_agents > 1:
            def mix(params):
                if faults is not None:
                    return C.mix_time_varying(params, fault_W_seq,
                                              state.step)
                if W is None:
                    return C.mix_hierarchical(params, W_intra, W_pod,
                                              state.step,
                                              tc.cross_pod_period)
                mesh = SH.current_mesh()
                rules = SH.current_rules() or {}
                agent_axes = rules.get("agent")
                if (mesh is not None and agent_axes
                        and C.is_uniform_complete(W)):
                    shapes = jax.eval_shape(lambda p: p, params)
                    specs = param_specs(shapes, rules, mesh,
                                        agent_stacked=True)
                    return C.mix_uniform_constrained(params, specs, mesh)
                return C.mix_stacked(params, W)
            if tc.consensus_interval > 1:
                params = jax.lax.cond(
                    jnp.mod(state.step, tc.consensus_interval) == 0,
                    mix, lambda p: p, params)
            else:
                params = mix(params)

        new_state = TrainState(params, opt_state, state.step + 1)
        out_metrics = {"loss": jnp.mean(loss), "grad_norm": gnorm,
                       "agent_loss": loss}
        out_metrics.update({k: jnp.mean(v) for k, v in metrics.items()})
        if tc.collect_metrics:
            # optimizer aux (||M||, ||delta||; its grad_norm is post-clip —
            # the pre-clip gnorm above wins the key)
            if isinstance(opt_state, dict):
                for k, v in opt_state.get("metrics", {}).items():
                    out_metrics.setdefault(k, v)
            out_metrics["consensus_error_pre_mix"] = \
                obs_metrics.consensus_error(pre_mix)
            # measured on the parameters as stored: without the barrier
            # XLA may feed the metric's agent mean the unrounded f32 mix
            # (excess precision), and on four v5e chips it then reported
            # disagreement between agents whose bf16 parameters were equal
            out_metrics["consensus_error"] = obs_metrics.consensus_error(
                jax.lax.optimization_barrier(params))
            out_metrics["param_norm"] = obs_metrics.global_norm(params)
            if faults is not None:
                t = jnp.mod(state.step, fault_u.shape[0])
                out_metrics.update({k: v[t]
                                    for k, v in fault_counters.items()})
        return new_state, out_metrics

    return train_step


def init_train_state(key, cfg: ModelConfig, tc: TrainConfig,
                     n_agents: int) -> TrainState:
    """Concrete init (small models / examples).  Per-agent param init uses
    distinct keys — the paper starts agents at distinct states."""
    opt = build_optimizer(tc)
    keys = jax.random.split(key, n_agents)
    params = jax.vmap(lambda k: T.init_params(k, cfg))(keys)
    return TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))


def abstract_train_state(cfg: ModelConfig, tc: TrainConfig,
                         n_agents: int) -> TrainState:
    """Shape-only TrainState (dry-run: no allocation)."""
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg, tc, n_agents),
        jax.random.key(0))


def train_state_specs(state_shapes: TrainState, cfg: ModelConfig,
                      rules: Dict[str, Any], mesh) -> TrainState:
    ps = param_specs(state_shapes.params, rules, mesh, agent_stacked=True)
    os_ = opt_state_specs(state_shapes.opt_state, ps, state_shapes.params,
                          mesh)
    os_ = sanitize_specs(os_, state_shapes.opt_state, mesh)
    return TrainState(ps, os_, jax.sharding.PartitionSpec())


def batch_specs(batch_shapes: Dict[str, Any], rules: Dict[str, Any],
                mesh) -> Dict[str, Any]:
    """Training batch: (A, B_local, S[, ...]) -> (agent, batch, None...)."""
    def one(leaf):
        nd = len(leaf.shape)
        axes = ("agent", "batch") + (None,) * (nd - 2)
        return SH.logical_to_spec(axes, rules)
    specs = jax.tree.map(one, batch_shapes)
    return sanitize_specs(specs, batch_shapes, mesh)
