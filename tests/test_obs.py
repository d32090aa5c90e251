"""Observability layer: sinks round-trip, aux metrics match hand-computed
values, and the disabled path is genuinely zero-cost (byte-identical jaxpr)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import consensus as C
from repro.core import memory as fmem
from repro.core.frodo import FrodoConfig, frodo
from repro.obs import metrics as M
from repro.obs import timing as OT
from repro.training.train_step import (TrainConfig, abstract_train_state,
                                       make_train_step)


# ------------------------------------------------------------------- sinks

def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with M.JsonlSink(path) as sink:
        sink.write({"step": 0, "loss": jnp.float32(1.5),
                    "gnorm": np.float64(2.0),
                    "vec": np.arange(3)})          # non-scalar: dropped
        sink.write({"step": 1, "loss": 0.75, "tag": "a"})
    rows = M.read_jsonl(path)
    assert rows == [{"step": 0, "loss": 1.5, "gnorm": 2.0},
                    {"step": 1, "loss": 0.75, "tag": "a"}]
    # every line is independently parseable (flush-per-write contract)
    with open(path) as f:
        assert all(json.loads(l) for l in f if l.strip())


def test_jsonl_sink_append_mode(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with M.JsonlSink(path) as s:
        s.write({"step": 0})
    with M.JsonlSink(path, mode="a") as s:
        s.write({"step": 1})
    assert [r["step"] for r in M.read_jsonl(path)] == [0, 1]


def test_jsonl_sink_empty_run(tmp_path):
    """A run that opens a sink and writes nothing still leaves a readable
    (empty) file — downstream tooling sees [] rather than ENOENT."""
    path = str(tmp_path / "empty.jsonl")
    with M.JsonlSink(path):
        pass
    assert M.read_jsonl(path) == []
    # double-close is harmless (context-manager + explicit close)
    sink = M.JsonlSink(path)
    sink.close()
    sink.close()
    assert M.read_jsonl(path) == []


def test_jsonl_sink_reopen_cycles(tmp_path):
    """Append/reopen across 'processes': records accumulate in order, and a
    final mode='w' reopen truncates (the benchmark-rerun contract)."""
    path = str(tmp_path / "m.jsonl")
    for step in range(3):
        with M.JsonlSink(path, mode="a") as s:
            s.write({"step": step})
    assert [r["step"] for r in M.read_jsonl(path)] == [0, 1, 2]
    with M.JsonlSink(path, mode="w") as s:
        s.write({"step": 99})
    assert [r["step"] for r in M.read_jsonl(path)] == [99]


def test_read_jsonl_skips_malformed_lines(tmp_path, caplog):
    """A run killed mid-write leaves a torn line; read-back skips it (and
    any other garbage) by default — counted on the result and warned about,
    never silently — and raises under strict=True."""
    import logging
    path = str(tmp_path / "torn.jsonl")
    with open(path, "w") as f:
        f.write('{"step": 0, "loss": 1.0}\n')
        f.write('not json at all\n')
        f.write('{"step": 1, "loss": 0.5}\n')
        f.write('{"step": 2, "los')               # torn mid-record
    with caplog.at_level(logging.WARNING, logger="repro.obs.metrics"):
        rows = M.read_jsonl(path)
    assert [r["step"] for r in rows] == [0, 1]
    assert rows.n_skipped == 2
    assert any("skipped 2 malformed line(s)" in r.message and path in r.message
               for r in caplog.records)
    with pytest.raises(json.JSONDecodeError):
        M.read_jsonl(path, strict=True)


def test_read_jsonl_clean_file_reports_zero_skipped(tmp_path, caplog):
    import logging
    path = str(tmp_path / "clean.jsonl")
    with open(path, "w") as f:
        f.write('{"step": 0}\n\n')                # blank line is not "torn"
    with caplog.at_level(logging.WARNING, logger="repro.obs.metrics"):
        rows = M.read_jsonl(path)
    assert rows == [{"step": 0}] and rows.n_skipped == 0
    assert not caplog.records


def test_memory_sink_and_default_record():
    sink = M.MemorySink()
    prev = M.set_sink(sink)
    try:
        M.record("bench.mix", 12.5, step=3, arch="h2o")
        assert M.get_sink() is sink
    finally:
        M.set_sink(prev)
    assert sink.records == [
        {"name": "bench.mix", "value": 12.5, "step": 3, "arch": "h2o"}]
    # after restore, record() goes to the previous (Null) sink: no error
    M.record("dropped", 0.0)


def test_scalarize_converts_and_drops():
    out = M.scalarize({"a": jnp.float32(2), "b": np.int64(3),
                       "c": np.ones((2,)), "d": "s"})
    assert out == {"a": 2.0, "b": 3, "d": "s"}
    assert all(type(v) in (float, int, str) for v in out.values())


def test_step_timer_counters():
    t = OT.StepTimer(items_per_step=10.0)
    assert t.tick() >= 0.0
    c1 = t.counters()
    assert set(c1) == {"step_time_ms", "wall_s", "throughput_items_per_s"}
    assert c1["step_time_ms"] >= 0.0
    t2 = OT.StepTimer()
    t2.tick()
    assert set(t2.counters()) == {"step_time_ms", "wall_s"}


def test_step_timer_throughput_quotes_ema():
    """The headline items/s comes off the EMA step time (stable under
    one-off stalls), not off the last step's time."""
    t = OT.StepTimer(items_per_step=100.0, ema=0.9)
    t.tick()
    # inject known step times instead of sleeping
    t.step_time_ms, t.ema_step_time_ms = 50.0, 10.0
    assert t.items_per_s == pytest.approx(100.0 / (10.0 * 1e-3))
    c = t.counters()
    assert c["throughput_items_per_s"] == pytest.approx(10000.0, abs=0.1)
    assert c["step_time_ms"] == pytest.approx(50.0)
    # first tick seeds the EMA with the first measurement
    t3 = OT.StepTimer(items_per_step=1.0)
    first = t3.tick()
    assert t3.ema_step_time_ms == pytest.approx(first)
    # zero-state edge: no division by zero before any tick
    t4 = OT.StepTimer(items_per_step=1.0)
    assert t4.items_per_s == 0.0


# --------------------------------------------------- jit-safe computations

def test_global_norm_hand_computed():
    tree = {"a": jnp.asarray([3.0, 0.0]), "b": jnp.asarray([[4.0]])}
    assert float(M.global_norm(tree)) == pytest.approx(5.0)
    assert float(M.global_norm({})) == 0.0


def test_consensus_error_hand_computed():
    x = np.asarray([[1.0, 2.0], [3.0, 6.0], [5.0, 4.0]])   # A=3, d=2
    mean = x.mean(0)
    expect = np.sqrt(np.mean(np.sum((x - mean) ** 2, axis=1)))
    got = float(M.consensus_error({"w": jnp.asarray(x)}))
    assert got == pytest.approx(expect, rel=1e-6)
    # at consensus it is exactly 0
    eq = jnp.broadcast_to(jnp.asarray([1.0, 2.0]), (3, 2))
    assert float(M.consensus_error({"w": eq})) == 0.0


def test_frodo_exact_metrics_match_hand_computed():
    """Two exact-mode steps; ||g||, ||M||, ||delta|| vs a numpy replay."""
    alpha, beta, lam, T = 0.5, 0.25, 0.5, 3
    cfg = FrodoConfig(alpha=alpha, beta=beta, lam=lam, T=T,
                      memory_mode="exact", collect_metrics=True)
    opt = frodo(cfg)
    g0 = np.asarray([1.0, -2.0, 2.0])
    g1 = np.asarray([0.5, 0.5, -1.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    assert set(state["metrics"]) == {"grad_norm", "memory_norm",
                                     "update_norm"}

    # step 1: empty history -> M = 0
    d, state = opt.update({"w": jnp.asarray(g0)}, state, params)
    assert float(state["metrics"]["grad_norm"]) == pytest.approx(
        np.linalg.norm(g0), rel=1e-6)
    assert float(state["metrics"]["memory_norm"]) == 0.0
    assert float(state["metrics"]["update_norm"]) == pytest.approx(
        alpha * np.linalg.norm(g0), rel=1e-6)

    # step 2: M = mu(1) * g0 with mu(1) = 1
    mu = fmem.mu_weights(T, lam)
    m1 = mu[0] * g0
    d, state = opt.update({"w": jnp.asarray(g1)}, state, params)
    assert float(state["metrics"]["memory_norm"]) == pytest.approx(
        np.linalg.norm(m1), rel=1e-6)
    expect_delta = -(alpha * g1 + beta * m1)
    np.testing.assert_allclose(np.asarray(d["w"]), expect_delta, rtol=1e-6)
    assert float(state["metrics"]["update_norm"]) == pytest.approx(
        np.linalg.norm(expect_delta), rel=1e-6)


def test_frodo_expsum_metrics_consistent():
    cfg = FrodoConfig(alpha=0.3, beta=0.1, lam=0.4, T=8, K=4,
                      memory_mode="expsum", collect_metrics=True)
    opt = frodo(cfg)
    g = {"w": jnp.asarray([1.0, 2.0])}
    state = opt.init(g)
    d1, state = opt.update(g, state, None)
    rates, coeffs = fmem.fit_expsum(cfg.T, cfg.lam, cfg.K)
    # first step: acc was zero -> M = 0, delta = -alpha g
    assert float(state["metrics"]["memory_norm"]) == 0.0
    d2, state = opt.update(g, state, None)
    m = np.asarray(fmem.expsum_memory_term(
        fmem.expsum_push(jnp.zeros((cfg.K, 2)), jnp.asarray(rates),
                         g["w"]), jnp.asarray(coeffs)))
    assert float(state["metrics"]["memory_norm"]) == pytest.approx(
        np.linalg.norm(m), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(d2["w"]), -(0.3 * np.asarray(g["w"]) + 0.1 * m),
        rtol=1e-5)


def test_mix_stacked_with_metrics():
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
    # uniform complete: post-mix error is exactly consensus
    Wu = np.full((4, 4), 0.25)
    out, aux = C.mix_stacked(x, Wu, with_metrics=True)
    assert float(aux["consensus_error_pre"]) == pytest.approx(
        float(M.consensus_error(x)), rel=1e-6)
    assert float(aux["consensus_error_post"]) < 1e-6
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(C.mix_stacked(x, Wu)["w"]))
    # general W branch: out == W @ x and pre-error matches hand computation
    Wg = np.asarray([[0.5, 0.5, 0.0], [0.0, 0.5, 0.5], [0.5, 0.0, 0.5]])
    x3 = {"w": jnp.asarray(rng.normal(size=(3, 2)), jnp.float32)}
    out3, aux3 = C.mix_stacked(x3, Wg, with_metrics=True)
    np.testing.assert_allclose(np.asarray(out3["w"]),
                               Wg @ np.asarray(x3["w"]), rtol=1e-5)
    xn = np.asarray(x3["w"])
    expect = np.sqrt(np.mean(np.sum((xn - xn.mean(0)) ** 2, axis=1)))
    assert float(aux3["consensus_error_pre"]) == pytest.approx(expect,
                                                               rel=1e-5)


# ------------------------------------------------------- zero-cost claims

def _plain_exact_update(cfg):
    """Hand-written FrODO exact update with NO metrics plumbing at all —
    the reference the instrumented-but-disabled build must lower to."""
    T_buf = max(cfg.pad_T, cfg.T)
    w = np.zeros(T_buf)
    w[:cfg.T] = fmem.mu_weights(cfg.T, cfg.lam, cfg.exponent_scale)
    weights = jnp.asarray(w, dtype=jnp.float32)

    def update(grads, state, params=None):
        cursor = jnp.mod(state["step"], T_buf)

        def leaf(g, h):
            m = fmem.exact_memory_term(h, cursor, weights)
            delta = -(cfg.alpha * g + cfg.beta * m.astype(g.dtype))
            return delta, fmem.exact_push(h, cursor, g)

        flat_g, treedef = jax.tree.flatten(grads)
        flat_h = treedef.flatten_up_to(state["hist"])
        out = [leaf(g, h) for g, h in zip(flat_g, flat_h)]
        return (treedef.unflatten([o[0] for o in out]),
                {"step": state["step"] + 1,
                 "hist": treedef.unflatten([o[1] for o in out])})

    return update


def test_frodo_disabled_metrics_jaxpr_byte_identical():
    """collect_metrics=False lowers to the same jaxpr as a build that never
    heard of metrics: instrumentation is free when off."""
    cfg = FrodoConfig(alpha=0.5, beta=0.25, lam=0.5, T=4,
                      memory_mode="exact", collect_metrics=False)
    opt = frodo(cfg)
    g = {"w": jnp.ones((3, 2)), "b": jnp.ones(3)}
    state = opt.init(g)
    instrumented = str(jax.make_jaxpr(opt.update)(g, state))
    plain = str(jax.make_jaxpr(_plain_exact_update(cfg))(g, state))
    assert instrumented == plain
    # sanity: turning collection ON does change the program
    opt_on = frodo(FrodoConfig(alpha=0.5, beta=0.25, lam=0.5, T=4,
                               memory_mode="exact", collect_metrics=True))
    state_on = opt_on.init(g)
    assert str(jax.make_jaxpr(opt_on.update)(g, state_on)) != plain


def test_mix_stacked_jaxpr_unchanged_by_metrics_flag_default():
    x = {"w": jnp.ones((3, 2))}
    W = np.asarray([[0.5, 0.5, 0.0], [0.0, 0.5, 0.5], [0.5, 0.0, 0.5]])
    base = str(jax.make_jaxpr(lambda v: C.mix_stacked(v, W))(x))
    off = str(jax.make_jaxpr(
        lambda v: C.mix_stacked(v, W, with_metrics=False))(x))
    assert base == off


def _tiny_cfg():
    return ModelConfig(n_layers=1, d_model=16, n_heads=2, n_kv_heads=1,
                       head_dim=8, d_ff=32, vocab=32,
                       param_dtype="float32", compute_dtype="float32")


def test_train_step_disabled_traces_no_metric_code(monkeypatch):
    """With collect_metrics=False no obs computation is ever traced: poison
    every metric entry point and trace the full train_step."""
    def boom(*a, **k):
        raise AssertionError("metric code traced with collect_metrics=False")

    monkeypatch.setattr(M, "frodo_step_metrics", boom)
    monkeypatch.setattr(M, "consensus_error", boom)
    monkeypatch.setattr(M, "global_norm", boom)
    monkeypatch.setattr(M, "zeros_like_metrics", boom)
    cfg = _tiny_cfg()
    tc = TrainConfig(T=4, memory_mode="exact", remat=False, ce_chunks=1)
    assert tc.collect_metrics is False
    state = abstract_train_state(cfg, tc, 2)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1, 8), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 1, 8), jnp.int32)}
    jax.eval_shape(make_train_step(cfg, tc, 2), state, batch)  # must not boom


def test_train_step_enabled_adds_metric_outputs():
    cfg = _tiny_cfg()
    tc_off = TrainConfig(T=4, memory_mode="exact", remat=False, ce_chunks=1)
    tc_on = TrainConfig(T=4, memory_mode="exact", remat=False, ce_chunks=1,
                        collect_metrics=True)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1, 8), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 1, 8), jnp.int32)}
    _, m_off = jax.eval_shape(make_train_step(cfg, tc_off, 2),
                              abstract_train_state(cfg, tc_off, 2), batch)
    _, m_on = jax.eval_shape(make_train_step(cfg, tc_on, 2),
                             abstract_train_state(cfg, tc_on, 2), batch)
    extra = set(m_on) - set(m_off)
    assert {"consensus_error", "consensus_error_pre_mix", "memory_norm",
            "update_norm", "param_norm"} <= extra
