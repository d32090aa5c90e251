"""Trainer loop: wires data pipeline, train step, metrics, checkpoints.

Telemetry: every step's scalar metrics (the aux pytree returned by
``train_step``, see ``TrainConfig.collect_metrics``) are merged with the
host-side step-timing counters and drained into ``sink`` (any
``obs.MetricsSink``).  ``metrics_file`` keeps the legacy end-of-run JSON
history; ``sink`` is the per-step JSONL/streaming path.

Host spans (``obs.span``, on a profiler trace's clock and in an installed
``SpanRecorder``): each step is ``train.step`` over ``train.data`` (next
batch), ``train.device_step`` (dispatch, inside the profiler's ``train``
step annotation), ``train.metrics`` (the metrics' device-to-host fetch) and
``train.log`` (sink, history, print); a checkpoint is ``checkpoint_save``.
While ``run`` runs, each garbage collection is a ``gc.gen<N>`` annotation.

The step donates the state it is given: a caller keeps only the state that
``run`` (or the step) returns.  Given a ``mesh`` with a ``data`` axis (see
``launch/mesh.py:make_host_mesh``), the agent axis of the state and the
batch is placed on ``data`` by ``train_step.train_state_specs`` and the step
is traced under the same logical-axis rules, so each device holds its
agents' share; without one, everything lives on the default device.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import obs
from repro.configs.base import ModelConfig
from repro.distributed import sharding as SH
from repro.training import checkpoint as ckpt
from repro.training.train_step import (TrainConfig, TrainState,
                                       abstract_train_state, build_rules,
                                       init_train_state, make_train_step,
                                       train_state_specs)


@dataclasses.dataclass
class Trainer:
    cfg: ModelConfig
    tc: TrainConfig
    n_agents: int
    n_pods: int = 1
    log_every: int = 10
    ckpt_every: int = 0
    ckpt_dir: str = "checkpoints"
    metrics_file: Optional[str] = None
    sink: Optional[obs.MetricsSink] = None
    tokens_per_step: float = 0.0   # for throughput_items_per_s in the sink
    profile_dir: Optional[str] = None   # jax.profiler capture target
    profile_start: int = 0              # capture window: steps
    profile_stop: int = 4               # [profile_start, profile_stop]
    mesh: Optional[Mesh] = None         # agents over its "data" axis

    def __post_init__(self):
        step = make_train_step(self.cfg, self.tc, self.n_agents, self.n_pods)
        self._history: list[Dict[str, Any]] = []
        if self.mesh is None:
            self.state_shardings = None
            self.step_fn = jax.jit(step, donate_argnums=0)
            return
        rules = build_rules(self.cfg, multi_pod=False)
        specs = train_state_specs(
            abstract_train_state(self.cfg, self.tc, self.n_agents), self.cfg,
            rules, self.mesh)
        self.state_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        batch_sharding = NamedSharding(
            self.mesh, SH.logical_to_spec(("agent",), rules))

        def step_on_mesh(state, batch):
            with SH.use_rules(rules, self.mesh):
                return step(state, batch)

        self.step_fn = jax.jit(
            step_on_mesh, in_shardings=(self.state_shardings, batch_sharding),
            out_shardings=(self.state_shardings, None), donate_argnums=0)

    def init(self, seed: int = 0) -> TrainState:
        key = jax.random.key(seed)
        if self.mesh is None:
            return init_train_state(key, self.cfg, self.tc, self.n_agents)
        return jax.jit(
            lambda k: init_train_state(k, self.cfg, self.tc, self.n_agents),
            out_shardings=self.state_shardings)(key)

    def run(self, state: TrainState, data: Iterator[Dict[str, np.ndarray]],
            steps: int) -> TrainState:
        timer = obs.StepTimer(items_per_step=self.tokens_per_step)
        prof = obs.ProfileWindow(self.profile_dir, self.profile_start,
                                 self.profile_stop)
        with obs.gc_spans():
            try:
                for i in range(steps):
                    prof.maybe_start(i)
                    t_step = time.perf_counter()
                    with obs.span("train.step", step=i):
                        with obs.span("train.data"):
                            batch = next(data)
                        t0 = time.perf_counter()
                        with obs.step_annotation("train", step=i), \
                                obs.span("train.device_step"):
                            state, metrics = self.step_fn(state, batch)
                            if (self.sink is not None
                                    or obs.get_recorder() is not None):
                                # block so the timer (and the span) measures
                                # the step, not the dispatch
                                jax.block_until_ready(metrics)
                        t1 = time.perf_counter()
                        timer.tick()
                        with obs.span("train.metrics"):
                            host = {k: np.asarray(v)
                                    for k, v in metrics.items()}
                            scalars = {k: float(v) for k, v in host.items()
                                       if v.ndim == 0}
                        t2 = time.perf_counter()
                        with obs.span("train.log"):
                            if self.sink is not None:
                                # per-agent vectors (agent_loss) ride along as
                                # lists; trajectory loaders read scalars only
                                vectors = {k: v.tolist()
                                           for k, v in host.items()
                                           if v.ndim == 1}
                                rec = dict(
                                    step=i, **scalars, **vectors,
                                    **timer.counters(),
                                    phase_data_ms=round(
                                        (t0 - t_step) * 1e3, 3),
                                    phase_step_ms=round((t1 - t0) * 1e3, 3),
                                    phase_metrics_ms=round((t2 - t1) * 1e3, 3))
                                self.sink.write(rec)
                            if i % self.log_every == 0 or i == steps - 1:
                                m = dict(scalars)
                                m.update(step=i, wall=round(timer.wall_s, 2))
                                self._history.append(m)
                                print(json.dumps(m), flush=True)
                    if self.ckpt_every and (i + 1) % self.ckpt_every == 0:
                        with obs.span("checkpoint_save"):
                            ckpt.save(
                                os.path.join(self.ckpt_dir, f"step{i+1}.npz"),
                                state.params, {"step": i + 1})
                    prof.maybe_stop(i)
            finally:
                prof.close()
        if self.metrics_file:
            os.makedirs(os.path.dirname(self.metrics_file) or ".",
                        exist_ok=True)
            with open(self.metrics_file, "w") as f:
                json.dump(self._history, f, indent=1)
        return state

    @property
    def history(self):
        return self._history
