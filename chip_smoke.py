"""Smoke run of the FrODO trainer on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # one chip: kernels, then training
    python chip_smoke.py --four-chips  # four chips: agents across chips

One process, no children.  It stops with a non-zero exit, and prints no
result, where JAX finds no TPU or any phase fails.  One chip:

* kernels — both fused FrODO updates (Pallas, compiled by Mosaic) on
  h2o-danube-1.8b leaf shapes, against the plain ``kernels/ref.py``; the
  exp-sum one in the chip's own layout of each leaf;
* train — the one-chip cut of h2o-danube-1.8b (``CHIP_TRAIN`` in its config
  module: published widths, 2 layers, 2 agents): 5 steps of the step the
  benchmark runs (metrics off, so the exp-sum update is the fused kernel)
  through ``Trainer.run``, then 2 with the optimizer's metrics on, which
  keep the jnp update, through ``launch.train.run_training``.  Every loss
  must be finite, the post-mix ``consensus_error`` about 0 on the complete
  graph, and the first two steps of both runs must agree.

``--four-chips`` runs only the four-agent step (``FOUR_CHIP_TRAIN``), once
with all four agents on one chip and once with one agent per chip, and
checks that the per-agent losses agree, that each chip holds a quarter of
the state, that the agents hold identical parameters after each mix, and
that the consensus mix compiled to an all-reduce.

Every number printed comes from this smoke run and is no benchmark.  The
last line of standard output is one JSON object naming the device.
Per-step records go to ``smoke_out/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "smoke_out"
ARCH = "h2o-danube-1.8b"
LABEL = "chip_smoke run (not a benchmark):"

# Agent-stacked h2o-danube-1.8b leaves: an MLP matrix, a 2-layer stack of
# k-projections (ragged last row block at T=40), a norm and the embedding
# (T cut so that the history fits beside its copies).
EXACT_CASES = [((2, 2560, 6912), 40), ((2, 2, 2560, 8, 80), 40),
               ((2, 2560), 40), ((2, 32000, 2560), 8)]
# (shape, K, accumulator dtype); the k-projection stack is laid out with
# its 2560 dim minor-most on the chip
EXPSUM_CASES = [((2, 2560, 6912), 8, "float32"),
                ((2, 2560, 6912), 4, "bfloat16"),
                ((2, 32000, 2560), 4, "bfloat16"),
                ((2, 2, 2560, 8, 80), 4, "bfloat16")]


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(LABEL, *parts, flush=True)


def peak_gb(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    if peak is None:
        return "not reported"
    return f"{peak / 1e9:.3f} GB" + (f" of {limit / 1e9:.3f} GB" if limit
                                     else "")


# ----------------------------------------------------------------- kernels

def phase_kernels(jax) -> None:
    import jax.numpy as jnp
    from repro.core import memory as fmem
    from repro.kernels import frodo_update as kfu
    from repro.kernels import ops, ref

    alpha, beta = 0.8, 0.35

    @jax.jit
    def worst(x, y, rtol, atol):
        """max(|x - y| - atol - rtol |y|): <= 0 where allclose holds."""
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        return jnp.max(jnp.abs(x - y) - atol - rtol * jnp.abs(y))

    def tol(dtype):
        return (2e-2, 2e-2) if dtype == jnp.bfloat16 else (1e-5, 1e-5)

    key = jax.random.key(0)
    for shape, T in EXACT_CASES:
        kg, kh, key = jax.random.split(key, 3)
        g = jax.random.normal(kg, shape, jnp.bfloat16)
        hist = jax.random.normal(kh, (T,) + shape, jnp.bfloat16)
        w = jnp.asarray(fmem.mu_weights(T, 0.15), jnp.float32)
        cursor = jnp.int32(T // 3)
        d1, h1 = ops.frodo_update(g, hist, cursor, w, alpha, beta)
        del h1
        d2, h2 = ref.frodo_update_ref(g, hist, cursor, w, alpha, beta)
        del h2, hist
        err = float(worst(d1, d2, *tol(jnp.bfloat16)))
        say(f"kernel exact T={T} {shape}: max excess error {err:.3e}")
        check(err <= 0, f"exact kernel {shape} disagrees with kernels/ref.py")
        del d1, d2

    device = jax.devices()[0]
    for shape, K, acc_dtype in EXPSUM_CASES:
        acc_dtype = jnp.dtype(acc_dtype)
        kg, ka, kp, key = jax.random.split(key, 4)
        g = jax.random.normal(kg, shape, jnp.bfloat16)
        acc = jax.random.normal(ka, (K,) + shape, acc_dtype)
        p = jax.random.normal(kp, shape, jnp.bfloat16)
        rates, coeffs = fmem.fit_expsum(40, 0.15, K)
        order = kfu.expsum_order(shape, jnp.bfloat16, acc_dtype, K, device)
        check(order is not None, f"expsum kernel does not tile {shape}")
        a2, p2 = ref.frodo_expsum_apply_ref(g, acc, p, 0.3, rates, coeffs,
                                            alpha, beta)
        a1, p1 = jax.jit(lambda g, a, p: kfu.expsum_apply(
            g, a, p, jnp.float32(0.3), rates=rates, coeffs=coeffs,
            alpha=alpha, beta=beta, order=order), donate_argnums=(1, 2))(
                g, acc, p)
        err_p = float(worst(p1, p2, *tol(jnp.bfloat16)))
        err_a = float(worst(a1, a2, *tol(acc_dtype)))
        say(f"kernel expsum K={K} {acc_dtype.name} {shape} order {order}: "
            f"max excess error params {err_p:.3e}, accumulators "
            f"{err_a:.3e}")
        check(err_p <= 0 and err_a <= 0,
              f"expsum kernel {shape} disagrees with kernels/ref.py")
        del g, p, a1, a2, p1, p2
    say(f"peak bytes in use after kernels: {peak_gb(jax)}")


# ------------------------------------------------------------------- train

def read_records(path: Path) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f]


def train_run(jax, name: str, steps: int, metrics: bool = True,
              **kw) -> tuple:
    """One training run of ``steps`` steps; returns (trainer, records, final
    state).  With ``metrics``, ``run_training`` with the optimizer's
    metrics; without, the same trainer and data through ``Trainer.run``
    with a sink and no metrics, the step the benchmark runs."""
    from repro import obs
    from repro.data.synthetic import TokenPipeline, augment_modalities
    from repro.launch.train import build_trainer, run_training

    path = OUT / f"{name}.jsonl"
    if metrics:
        trainer, state = run_training(arch=ARCH, steps=steps,
                                      metrics_out=str(path), seed=0, **kw)
    else:
        sink = obs.JsonlSink(str(path))
        trainer = build_trainer(arch=ARCH, sink=sink, log_every=5, **kw)
        data = augment_modalities(iter(TokenPipeline(
            vocab=trainer.cfg.vocab, seq_len=kw["seq"],
            batch_per_agent=kw["batch_per_agent"], n_agents=kw["agents"],
            seed=0)), trainer.cfg)
        state = trainer.run(trainer.init(seed=0), data, steps)
        sink.close()
    recs = read_records(path)
    check(len(recs) == steps, f"{name}: {len(recs)} records, want {steps}")
    step_ms = [r["phase_step_ms"] for r in recs]
    for r in recs:
        say(f"{name} step {r['step']}: loss {r['loss']:.6f} agent_loss "
            f"{r['agent_loss']} consensus_error "
            f"{r.get('consensus_error', float('nan')):.3e} "
            f"step {r['phase_step_ms']:.1f} ms (after block_until_ready)")
    say(f"{name}: first step {step_ms[0]:.1f} ms (trace + compile + run)")
    if len(step_ms) > 1:
        steady = statistics.median(step_ms[1:])
        say(f"{name}: steady step {steady:.1f} ms (median of steps 1-"
            f"{steps - 1}); compile about {step_ms[0] - steady:.1f} ms")
    say(f"{name}: peak bytes in use {peak_gb(jax)}")
    return trainer, recs, state


def check_records(name: str, recs: list) -> None:
    """Finite losses, and agents that agree after each mix (the graph is
    complete) where the run has metrics."""
    for r in recs:
        check(all(math.isfinite(x) for x in r["agent_loss"]),
              f"{name}: non-finite loss at step {r['step']}")
        if "consensus_error" not in r:
            continue
        check(abs(r["consensus_error"]) <= 1e-6,
              f"{name}: post-mix consensus_error {r['consensus_error']} "
              "on a complete graph")


def agree(a: list, b: list, what: str, rtol=1e-2, atol=1e-2) -> None:
    """Per-agent losses of two runs of one step, to bf16 tolerance."""
    for ra, rb in zip(a, b):
        for x, y in zip(ra["agent_loss"], rb["agent_loss"]):
            check(abs(x - y) <= atol + rtol * abs(y),
                  f"{what}: step {ra['step']} agent losses {ra['agent_loss']}"
                  f" vs {rb['agent_loss']}")


def phase_train(jax) -> None:
    from repro.configs.h2o_danube_1_8b import CHIP_TRAIN, reduced
    from repro.configs import registry as REG

    cfg = REG.reduced_layers(REG.get_config(ARCH), CHIP_TRAIN["layers"])
    say(f"config {ARCH} at published widths (d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab},"
        f" window {cfg.window}); cut {reduced}; run {CHIP_TRAIN}")
    _, fused, state = train_run(jax, "train_fused", 5, metrics=False,
                                **CHIP_TRAIN)
    del state
    check_records("train_fused", fused)
    _, plain, state = train_run(jax, "train_jnp", 2, **CHIP_TRAIN)
    del state
    check_records("train_jnp", plain)
    agree(fused[:2], plain, "fused vs jnp update")


def phase_four_chips(jax) -> None:
    import numpy as np
    from repro.configs.h2o_danube_1_8b import FOUR_CHIP_TRAIN

    say(f"four-chip run {FOUR_CHIP_TRAIN} on {len(jax.devices())} devices")
    steps = 3
    _, one, state = train_run(jax, "four_agents_one_chip", steps,
                              **FOUR_CHIP_TRAIN)
    del state
    check_records("four_agents_one_chip", one)
    trainer, spread, state = train_run(jax, "four_agents_four_chips", steps,
                                       mesh=True, **FOUR_CHIP_TRAIN)
    apart = 0.0
    for leaf in jax.tree.leaves(state.params):
        rows = [np.asarray(s.data, np.float32)
                for s in leaf.addressable_shards]
        apart = max(apart, *(float(np.abs(r - rows[0]).max()) for r in rows))
    say(f"largest gap between two chips' agents after the last mix: {apart}")
    check(apart == 0, "the agents' parameters differ after the mix")
    check_records("four_agents_four_chips", spread)
    agree(spread, one, "four chips vs one chip")
    say("per-agent losses agree with the one-chip run")

    per_dev = {d.id: 0 for d in jax.devices()}
    total = 0
    for leaf in jax.tree.leaves(state):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    say(f"state bytes per device {per_dev} of {total} in all")
    quarter = total / len(per_dev)
    check(all(abs(b - quarter) <= 1e-3 * quarter for b in per_dev.values()),
          f"state is not split evenly over the chips: {per_dev}")

    A, B, S = (FOUR_CHIP_TRAIN[k] for k in ("agents", "batch_per_agent",
                                             "seq"))
    batch = {k: np.zeros((A, B, S), np.int32) for k in ("tokens", "labels")}
    hlo = trainer.step_fn.lower(state, batch).compile().as_text()
    # per-agent shapes of the unstacked matrices (embedding, head)
    leaf_shapes = {",".join(map(str, leaf.shape[1:]))
                   for leaf in jax.tree.leaves(state.params) if leaf.ndim == 3}
    del state
    colls = {k: [ln for ln in hlo.splitlines()
                 if re.search(rf"\b{k}(-start)?\(", ln)]
             for k in ("all-reduce", "all-gather", "collective-permute",
                       "all-to-all", "reduce-scatter")}
    say("collectives in the compiled step:",
        {k: len(v) for k, v in colls.items()})
    check(any(s in ln for ln in colls["all-reduce"] for s in leaf_shapes),
          "no all-reduce of a parameter leaf: the consensus mix is missing")
    check(not any(s in ln for ln in colls["all-gather"] for s in leaf_shapes),
          "the consensus mix gathers the agents' parameters")
    say("consensus mix compiled to an all-reduce of the parameters")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the agents-across-four-chips phase")
    args = ap.parse_args()

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no accelerator: {e}")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); this script runs only on one")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        sys.exit(f"chip_smoke: {len(devices)} TPU device(s), need {want}")

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: the repro package is not beside this script "
                 f"({e})")
    say(f"compile cache at {use_compile_cache()}")
    OUT.mkdir(parents=True, exist_ok=True)
    say(f"device {devices[0].device_kind} x{len(devices)}")

    try:
        if args.four_chips:
            phase_four_chips(jax)
        else:
            phase_kernels(jax)
            phase_train(jax)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")

    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)


if __name__ == "__main__":
    main()
