"""One run of one training cell: set-up, the measured window, the check.

Set-up builds the trainer as ``launch/train.py:run_training`` builds it,
gives it the benchmark's weights (one jitted call from the seed) and the
program's own optimizer state for them, and drives it through the first
steps with ``Trainer.run`` and the cell's token stream.  Those steps
compile the step program and are the steps the reference re-computes.
The same trainer and state then run in chunks of ``Trainer.run`` until
``seconds`` have passed.  Afterwards, with the program's state freed, the
float32 reference re-runs the first steps from the same weights and
tokens, and ``check`` compares the two.

Besides the clock and the trace, a metric's reader gets what the program
counts: the trainer's step records of the window (``history``: every
``log_every``-th step's scalars, as ``Trainer.history`` keeps them) and the
program's ``obs.record`` records of set-up and the window (``records``).
"""
from __future__ import annotations

import contextlib
import gc
import glob
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from harness import check, peaks, reference, scopes, spec, tokens, tree
from harness import trace_reduce, weights


class NoAccelerator(Exception):
    pass


class Feed:
    """The token stream as the trainer sees it, with the time each batch
    was asked for: one step runs from one request to the next."""

    def __init__(self, stream):
        self.stream = stream
        self.marks: list = []

    def __iter__(self):
        return self

    def __next__(self):
        import jax
        t = time.perf_counter()
        self.marks.append(t)
        with jax.profiler.TraceAnnotation("bench.data"):
            return next(self.stream)


class CompileCounter:
    """Counts compilations (and compile-cache loads) while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        self._event(event)

    def _event(self, event, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1


class GcPauses:
    """Garbage collections while ``on``, by generation: how many, and
    their total and longest pause in ms."""

    def __init__(self):
        self.on = False
        self.t0 = 0.0
        self.by_gen: dict = {}
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
        elif self.on:
            ms = (time.perf_counter() - self.t0) * 1e3
            n, total, most = self.by_gen.get(info["generation"], (0, 0., 0.))
            self.by_gen[info["generation"]] = (n + 1, total + ms,
                                               max(most, ms))


def devices_for(chips: int, require_accelerator: bool):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX finds no devices: {e}") from None
    if require_accelerator:
        if devs[0].platform != "tpu":
            raise NoAccelerator(f"JAX finds no TPU (platform "
                                f"{devs[0].platform!r})")
        if len(devs) < chips:
            raise NoAccelerator(f"{len(devs)} TPU device(s); the cell "
                                f"needs {chips}")
        peaks.peaks_for(devs[0].device_kind)
    return devs


def fits(want, have) -> bool:
    """Whether the trees ``want`` (the program's parameters) and ``have``
    (the benchmark's) match in structure and in each leaf's shape and
    dtype."""
    import jax
    return jax.tree.structure(want) == jax.tree.structure(have) and all(
        (a.shape, a.dtype) == (b.shape, b.dtype)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)))


@contextlib.contextmanager
def program_records():
    """The program's ``obs.record`` records while inside, as a list; the
    sink that was set before is set again after."""
    from repro import obs
    sink = obs.MemorySink()
    before = obs.set_sink(sink)
    try:
        yield sink.records
    finally:
        obs.set_sink(before)


def quiet():
    """The trainer logs to standard output; the result line must be last
    there, so its lines go to standard error."""
    return contextlib.redirect_stdout(sys.stderr)


class Session:
    """The program's trainer for one cell, built once, and the two halves
    of a check that share it: the program's first steps from a seed, and
    the reference's."""

    def __init__(self, root: Path, cell_name: str,
                 require_accelerator: bool = True):
        self.cell = cell = spec.Cell(root, cell_name)
        import jax
        import jax.numpy as jnp

        self.devs = devices_for(cell.chips, require_accelerator)
        self.counter = CompileCounter()
        sys.path.insert(0, str(Path(root) / "src"))
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import build_trainer
        from repro.training.train_step import (TrainState,
                                               abstract_train_state,
                                               build_optimizer)

        self.TrainState = TrainState
        cfg, traffic = cell.config, cell.traffic
        m, tr = cfg["model"], cfg["trainer"]
        self.A = A = tr["agents"]
        B, S = traffic["batch_per_agent"], traffic["seq_len"]
        self.tokens_per_step = A * B * S
        self.mesh = mesh = make_host_mesh() if cfg["mesh"] else None
        if mesh is not None:
            shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            if shape != cfg["mesh"]:
                raise NoAccelerator(f"host mesh {shape}, the cell needs "
                                    f"{cfg['mesh']}")
        self.trainer = build_trainer(**tr, seq=S, batch_per_agent=B,
                                     mesh=mesh)
        cell.model.check_model(self.trainer.cfg, m)
        opt = build_optimizer(self.trainer.tc)
        self.shard = shard = self.trainer.state_shardings
        shapes = cell.model.leaf_shapes(m)
        self.init_params = jax.jit(
            lambda key: weights.stacked_params(key, shapes,
                                               m["param_dtype"], A),
            **({"out_shardings": shard.params} if shard else {}))
        self.init_opt = jax.jit(
            opt.init, **({"out_shardings": shard.opt_state} if shard else {}))
        want = abstract_train_state(self.trainer.cfg, self.trainer.tc,
                                    A).params
        have = jax.eval_shape(self.init_params, weights.seed_key(0))
        if not fits(want, have):
            raise ValueError("the benchmark's weights do not fit the "
                             "program's parameter tree")

        rates, _ = reference.expsum_fit(tr["T"], tr["lam"], tr["K"])
        kmax = int(np.argmax(rates))

        @jax.jit
        def first_grad_norms(acc):
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                v[kmax].astype(jnp.float32) / rates[kmax]),
                axis=tuple(range(1, v.ndim - 1))))
                for k, v in tree.flatten(acc).items()}

        @jax.jit
        def change_norms(p, p0):
            p, p0 = tree.flatten(p), tree.flatten(p0)
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                p[k].astype(jnp.float32) - p0[k].astype(jnp.float32)),
                axis=tuple(range(1, p[k].ndim)))) for k in p}

        self.first_grad_norms = first_grad_norms
        self.change_norms = change_norms

    def per_agent(self, norms: dict) -> list:
        host = {k: np.asarray(v, np.float64) for k, v in norms.items()}
        return [{k: float(v[a]) for k, v in host.items()}
                for a in range(self.A)]

    def start(self, seed: int) -> tuple:
        """Weights from the seed, the program's optimizer state for them,
        and the first steps through ``Trainer.run`` on the cell's stream.
        Returns (state, feed, readings, seconds spent on the readings)."""
        import jax
        import jax.numpy as jnp

        traffic, m = self.cell.traffic, self.cell.config["model"]
        key = weights.seed_key(seed)
        params = self.init_params(key)
        step0 = jnp.zeros((), jnp.int32)
        if self.shard:
            step0 = jax.device_put(step0, self.shard.step)
        state = self.TrainState(params, self.init_opt(params), step0)
        del params
        feed = Feed(tokens.make_stream(traffic, m["vocab"], self.A, seed))
        prog = {"losses": []}
        excluded = 0.0
        for t in range(traffic["check_steps"]):
            with quiet():
                state = self.trainer.run(state, feed, 1)
            prog["losses"].append(float(self.trainer.history[-1]["loss"]))
            if t == 0:
                t0 = time.perf_counter()
                prog["first_grad"] = self.per_agent(
                    self.first_grad_norms(state.opt_state["acc"]))
                excluded += time.perf_counter() - t0
        t0 = time.perf_counter()
        p0 = self.init_params(key)
        prog["change"] = self.per_agent(self.change_norms(state.params, p0))
        del p0
        excluded += time.perf_counter() - t0
        return state, feed, prog, excluded

    def reference(self, seed: int, quant=None, fault=None) -> dict:
        """The reference's first steps from the same weights and tokens;
        run it with the program's state freed."""
        traffic, cfg, model = (self.cell.traffic, self.cell.config,
                               self.cell.model)
        devs = (list(self.mesh.devices.flat) if self.mesh is not None
                else self.devs[:1])
        stacked = tree.flatten(self.init_params(weights.seed_key(seed)))
        params = reference.agent_slices(stacked, self.A,
                                        model.stacks(cfg["model"]), devs)
        del stacked
        stream = tokens.make_stream(traffic, cfg["model"]["vocab"], self.A,
                                    seed)
        batches = [next(stream) for _ in range(traffic["check_steps"])]
        return reference.run(model, params, batches, cfg, devs, quant=quant,
                             fault=fault)

    def peak_bytes(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devs[:self.cell.chips]))


def run(root: Path, cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_accelerator: bool = True) -> tuple:
    """Returns (result, checks, info): the result line's object, the
    compared numbers with their limits, and notes for standard error."""
    import jax

    ses = Session(root, cell_name, require_accelerator)
    cell, trainer = ses.cell, ses.trainer
    with program_records() as records:
        state, feed, prog, excluded = ses.start(seed)
        setup_s = time.perf_counter() - t_start - excluded

        # the measured window
        chunk = int(cell.traffic["chunk_steps"])
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        feed.marks.clear()
        logged = len(trainer.history)
        bounds = []
        collections = GcPauses()
        ses.counter.on = collections.on = True
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with quiet():
                    state = trainer.run(state, feed, chunk)
                bounds.append((len(feed.marks), time.perf_counter()))
                if bounds[-1][1] - w0 >= seconds:
                    break
        w1 = bounds[-1][1]
        ses.counter.on = collections.on = False
    if trace:
        jax.profiler.stop_trace()
    step_s, first = [], 0
    for n, end in bounds:
        marks = feed.marks[first:n] + [end]
        step_s += [b - a for a, b in zip(marks, marks[1:])]
        first = n
    history = trainer.history[logged:]
    window_losses = [h["loss"] for h in history]
    peak = ses.peak_bytes()
    del state
    gc.collect()

    reduced = None
    if trace:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        record = trace_reduce.load(files[0])
        reduced = trace_reduce.reduce(record)
        del record
        shutil.rmtree(trace_dir, ignore_errors=True)

    t0 = time.perf_counter()
    ref = ses.reference(seed)
    ref_s = time.perf_counter() - t0
    correct, checks = check.judge(check.compare(prog, ref), cell.limits)

    devs = ses.devs
    run_rec = {"window_s": w1 - w0, "steps": len(step_s), "step_s": step_s,
               "tokens_per_step": ses.tokens_per_step, "setup_s": setup_s,
               "trace": reduced, "config": cell.config,
               "traffic": cell.traffic, "model": cell.model,
               "history": history, "records": records,
               "peaks": peaks.PEAKS.get(devs[0].device_kind),
               "chips": cell.chips,
               "flops_per_token": cell.model.train_flops_per_token(
                   cell.config["model"], cell.traffic["seq_len"])}
    metrics = cell.read_metrics("per_layer" if trace else "end_to_end",
                                run_rec)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(step_s),
              "failed": sum(not math.isfinite(x) for x in window_losses),
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_ns"] * 1e-9
        device["window_s"] = reduced["window_ns"] * 1e-9
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    info = {"compiles_in_window": ses.counter.count,
            "gc_in_window": collections.by_gen, "setup_s": setup_s,
            "excluded_s": excluded, "reference_s": ref_s,
            "steps": len(step_s), "window_s": w1 - w0,
            "slowest_steps_ms": sorted(x * 1e3 for x in step_s)[-5:],
            "median_step_ms": float(np.median(step_s)) * 1e3,
            "ref_losses": ref["losses"], "prog_losses": prog["losses"]}
    if reduced is not None:
        # the fused exp-sum kernel's share of the update's device time
        update = scopes.scope_ms(reduced, "frodo.update")
        kernel = scopes.scope_ms(reduced, "pallas.frodo_expsum_apply")
        info["expsum_kernel_ms"] = kernel
        info["expsum_kernel_share"] = (kernel / update if kernel and update
                                       else None)
    return result, checks, info
