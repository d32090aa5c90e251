"""Step timing, throughput counters, and profiler trace annotation.

``StepTimer`` is the host-side clock the trainer / serving engine / bench
drivers share: call ``tick()`` once per completed step (AFTER blocking on
the step's outputs — an async dispatch that hasn't materialised yet would
time the enqueue, not the work) and read ``step_time_ms`` / throughput.

Host-side regions go on a captured trace through ``obs.span``
(``repro.obs.spans``); ``trace_scope`` is the in-jit equivalent
(``jax.named_scope``) around the train step's forward/backward, the FrODO
update, the Pallas kernel path and the consensus mixes.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

import jax


@contextlib.contextmanager
def step_annotation(name: str, step: int) -> Iterator[None]:
    """``StepTraceAnnotation`` — lets the profiler group a whole train step."""
    try:
        ctx = jax.profiler.StepTraceAnnotation(name, step_num=step)
    except Exception:                                    # pragma: no cover
        ctx = contextlib.nullcontext()
    with ctx:
        yield


def trace_scope(name: str):
    """In-jit named scope: tags the emitted HLO so kernel/collective ops are
    attributable in profiles.  Safe under tracing (pure metadata)."""
    try:
        return jax.named_scope(name)
    except Exception:                                    # pragma: no cover
        return contextlib.nullcontext()


class StepTimer:
    """Wall-clock per step + exponential moving average + items/s.

    ``items_per_step`` is whatever unit throughput should be quoted in
    (tokens, samples, decoded tokens); pass 0 to skip throughput.
    """

    def __init__(self, items_per_step: float = 0.0, ema: float = 0.9) -> None:
        self.items_per_step = items_per_step
        self._ema_coef = ema
        self.reset()

    def reset(self) -> None:
        self._last: Optional[float] = None
        self._t0 = time.perf_counter()
        self.steps = 0
        self.step_time_ms = 0.0
        self.ema_step_time_ms = 0.0

    def tick(self) -> float:
        """Mark one completed step; returns this step's wall ms."""
        now = time.perf_counter()
        prev = self._last if self._last is not None else self._t0
        self._last = now
        self.step_time_ms = (now - prev) * 1e3
        self.ema_step_time_ms = (
            self.step_time_ms if self.steps == 0 else
            self._ema_coef * self.ema_step_time_ms
            + (1 - self._ema_coef) * self.step_time_ms)
        self.steps += 1
        return self.step_time_ms

    @property
    def wall_s(self) -> float:
        return (self._last or time.perf_counter()) - self._t0

    @property
    def items_per_s(self) -> float:
        """Throughput off the EMA step time: the per-step value jitters
        with scheduler noise and GC pauses."""
        if not self.items_per_step or self.ema_step_time_ms <= 0:
            return 0.0
        return self.items_per_step / (self.ema_step_time_ms * 1e-3)

    def counters(self) -> Dict[str, float]:
        """The standard keys trainers merge into each metrics record."""
        out = {"step_time_ms": round(self.step_time_ms, 3),
               "wall_s": round(self.wall_s, 3)}
        if self.items_per_step:
            out["throughput_items_per_s"] = round(self.items_per_s, 1)
        return out


class ProfileWindow:
    """Programmatic ``jax.profiler`` capture over a step window.

    Drivers call ``maybe_start(step)`` / ``maybe_stop(step)`` around each
    step; the trace starts at ``start`` and stops after ``stop``
    (inclusive), landing a TensorBoard/Perfetto-loadable device trace in
    ``profile_dir``.  Inert when ``profile_dir`` is None.  ``close()``
    stops a still-open capture (loops shorter than the window).
    """

    def __init__(self, profile_dir: Optional[str], start: int = 0,
                 stop: int = 4) -> None:
        self.profile_dir = profile_dir
        self.start = start
        self.stop = stop
        self._active = False

    def maybe_start(self, step: int) -> None:
        if (self.profile_dir is None or self._active
                or step != self.start):
            return
        try:
            jax.profiler.start_trace(self.profile_dir)
            self._active = True
        except Exception:                                # pragma: no cover
            self.profile_dir = None

    def maybe_stop(self, step: int) -> None:
        if not self._active or step < self.stop:
            return
        self.close()

    def close(self) -> None:
        if not self._active:
            return
        self._active = False
        try:
            jax.profiler.stop_trace()
        except Exception:                                # pragma: no cover
            pass
