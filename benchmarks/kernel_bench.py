"""Kernel micro-benchmarks: fused FrODO update (Pallas) vs the unfused
pure-jnp reference, plus the analytic HBM-traffic model that motivates the
fusion on TPU (the derived column is the modelled HBM bytes moved per step,
which is hardware-independent).  The Pallas rows need a TPU: off it the
kernels raise rather than fall back to interpret mode."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import memory as fmem
from repro.kernels import frodo_update as kfu
from repro.kernels import ops, ref
from repro.obs.spans import span


def _time(fn, *args, reps=2, name="kernel"):
    with span(f"kernel_bench.{name}.warmup"):
        fn(*args)                                # compile/warm
    with span(f"kernel_bench.{name}", reps=reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    return dt / reps * 1e6


def traffic_model(n, T=None, K=None, itemsize=4):
    """HBM bytes per step: fused = single pass; unfused = extra M write+read."""
    if T is not None:
        fused = (T + 3) * n * itemsize           # hist + g + x rw
        unfused = (T + 5) * n * itemsize         # + materialize M
    else:
        fused = (2 * K + 3) * n * itemsize
        unfused = (2 * K + 5) * n * itemsize
    return fused, unfused


def rows(seed=0):
    out = []
    rng = np.random.default_rng(seed)
    for n in (1 << 14, 1 << 17):
        T, K = 32, 8
        g = jnp.asarray(rng.normal(size=n), jnp.float32)
        hist = jnp.asarray(rng.normal(size=(T, n)), jnp.float32)
        w = jnp.asarray(fmem.mu_weights(T, 0.15), jnp.float32)
        cur = jnp.int32(3)
        jr = jax.jit(lambda g, h: ref.frodo_update_ref(g, h, cur, w, 0.8,
                                                       0.35))
        us_ref = _time(jr, g, hist, name=f"exact_jnp_n{n}")
        us_ker = _time(lambda g, h: ops.frodo_update(g, h, cur, w, 0.8,
                                                     0.35), g, hist,
                       name=f"exact_pallas_n{n}")
        fused, unfused = traffic_model(n, T=T)
        out.append((f"frodo_exact_jnp_n{n}", us_ref, f"hbm_bytes={unfused}"))
        out.append((f"frodo_exact_pallas_n{n}(interp)", us_ker,
                    f"hbm_bytes={fused}"))
        shape = (n // 1024, 1024)
        g2 = g.reshape(shape)
        acc = jnp.asarray(rng.normal(size=(K,) + shape), jnp.float32)
        rates, coeffs = fmem.fit_expsum(90, 0.15, K)
        jr2 = jax.jit(lambda g, a, p: ref.frodo_expsum_apply_ref(
            g, a, p, 1.0, rates, coeffs, 0.8, 0.35))
        us_ref2 = _time(jr2, g2, acc, g2, name=f"expsum_jnp_n{n}")
        us_ker2 = _time(jax.jit(lambda g, a, p: kfu.expsum_apply(
            g, a, p, jnp.float32(1), rates=rates, coeffs=coeffs, alpha=0.8,
            beta=0.35)), g2, acc, g2, name=f"expsum_pallas_n{n}")
        fused, unfused = traffic_model(n, K=K)
        out.append((f"frodo_expsum_jnp_n{n}", us_ref2,
                    f"hbm_bytes={unfused}"))
        out.append((f"frodo_expsum_pallas_n{n}(interp)", us_ker2,
                    f"hbm_bytes={fused}"))
    return out
