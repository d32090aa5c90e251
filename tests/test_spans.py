"""Span profiler: nesting/aggregation invariants, Chrome-trace schema,
and the low cost of the disabled path (no recorder installed => a profiler
annotation only, nothing retained, and the traced train-step jaxpr is
byte-identical to a build that never heard of spans).

``hypothesis`` is an optional dev dependency: the property tests are
skipped when it is absent (the deterministic tests still pin the core
invariants).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                                      # pragma: no cover
    hypothesis = None

from repro.obs import spans as S


# ------------------------------------------------------------- recording

def test_recorder_records_nesting_and_durations():
    with S.SpanRecorder() as rec:
        with S.span("outer", step=3):
            with S.span("inner_a"):
                pass
            with S.span("inner_b"):
                pass
    assert [sp.name for sp in rec.spans] == ["outer", "inner_a", "inner_b"]
    outer, a, b = rec.spans
    assert outer.parent == -1 and outer.depth == 0
    assert a.parent == 0 and a.depth == 1
    assert b.parent == 0 and b.depth == 1
    assert outer.args == {"step": 3}
    # children are contained in the parent interval
    for child in (a, b):
        assert child.dur_ns >= 0
        assert child.start_ns >= outer.start_ns
        assert (child.start_ns + child.dur_ns
                <= outer.start_ns + outer.dur_ns)
    # siblings don't overlap
    assert b.start_ns >= a.start_ns + a.dur_ns
    assert S.span_paths(rec.spans) == ["outer", "outer/inner_a",
                                       "outer/inner_b"]


def test_recorder_install_restore_and_noop_when_absent():
    assert S.get_recorder() is None
    handle = S.span("anything", step=1)
    # disabled path: a profiler annotation, nothing recorded anywhere
    with handle:
        pass
    assert handle.sync("tree") == "tree"
    outer = S.SpanRecorder()
    with outer:
        assert S.get_recorder() is outer
        inner = S.SpanRecorder()
        with inner:
            assert S.get_recorder() is inner
            with S.span("x"):
                pass
        assert S.get_recorder() is outer          # restored, not cleared
    assert S.get_recorder() is None
    assert [sp.name for sp in inner.spans] == ["x"]
    assert outer.spans == []


def test_end_tolerates_unclosed_children():
    rec = S.SpanRecorder()
    i_outer = rec.begin("outer")
    rec.begin("leaked")                   # never explicitly ended
    rec.end(i_outer)
    leaked = rec.spans[1]
    assert leaked.dur_ns >= 0             # closed at the parent's end
    assert rec._stack() == []             # stack not corrupted
    # recorder remains usable
    with S.span("after"):
        pass                              # no recorder installed: no-op
    i2 = rec.begin("next")
    rec.end(i2)
    assert rec.spans[-1].name == "next" and rec.spans[-1].parent == -1


# ------------------------------------------------------------ aggregation

def _make_spans(tree, t0=0):
    """Build a synthetic span list from [(name, dur, children), ...]."""
    spans, clock = [], [t0]

    def emit(nodes, depth, parent):
        for name, dur, children in nodes:
            idx = len(spans)
            start = clock[0]
            spans.append(S.Span(name=name, start_ns=start, dur_ns=dur,
                                depth=depth, parent=parent, tid=1))
            emit(children, depth + 1, idx)
            clock[0] = start + dur
    emit(tree, 0, -1)
    return spans


def test_aggregate_totals_equal_self_plus_children():
    ms = 1_000_000
    spans = _make_spans([
        ("step", 10 * ms, [("data", 2 * ms, []),
                           ("compute", 5 * ms, [("kernel", 4 * ms, [])])]),
        ("step", 20 * ms, [("data", 3 * ms, []),
                           ("compute", 12 * ms, [("kernel", 10 * ms, [])])]),
    ])
    agg = S.aggregate(spans)
    assert set(agg) == {"step", "step/data", "step/compute",
                        "step/compute/kernel"}
    # invariant: total == self + sum(direct children totals), per path
    for path, stat in agg.items():
        child_total = sum(s.total_ms for p, s in agg.items()
                          if p.rsplit("/", 1)[0] == path and p != path)
        assert stat.total_ms == pytest.approx(stat.self_ms + child_total)
    st_ = agg["step"]
    assert st_.count == 2 and st_.total_ms == pytest.approx(30.0)
    assert agg["step/compute"].pct_of_parent == pytest.approx(17 / 30)
    assert agg["step/compute/kernel"].pct_of_root == pytest.approx(14 / 30)
    assert st_.pct_of_parent == 1.0 and st_.pct_of_root == 1.0
    assert agg["step"].p50_ms == pytest.approx(15.0)


def test_aggregate_open_spans_count_as_zero():
    spans = [S.Span("open", 0, -1, 0, -1, 1)]
    agg = S.aggregate(spans)
    assert agg["open"].total_ms == 0.0


if hypothesis is not None:

    node = st.deferred(lambda: st.tuples(
        st.sampled_from(["a", "b", "c"]),
        st.integers(min_value=0, max_value=10 ** 9),
        st.lists(node, max_size=3)))

    @settings(deadline=None, max_examples=30)
    @given(st.lists(node, min_size=1, max_size=4))
    def test_aggregate_invariants_random_trees(tree):
        spans = _make_spans(tree)
        paths = S.span_paths(spans)
        agg = S.aggregate(spans)
        # parents precede children; every parent path exists
        for sp, path in zip(spans, paths):
            if sp.parent >= 0:
                assert paths[sp.parent] == path.rsplit("/", 1)[0]
        for path, stat in agg.items():
            child_total = sum(s.total_ms for p, s in agg.items()
                              if "/" in p and p.rsplit("/", 1)[0] == path)
            assert stat.total_ms == pytest.approx(
                stat.self_ms + child_total, abs=1e-9)
            assert stat.pct_of_parent >= 0.0
            assert stat.count == sum(p == path for p in paths)
        # grand total conservation: sum of root totals == sum of root durs
        root_total = sum(s.total_ms for p, s in agg.items() if "/" not in p)
        assert root_total == pytest.approx(
            sum(sp.dur_ns for sp in spans if sp.parent < 0) / 1e6)

    @settings(deadline=None, max_examples=30)
    @given(st.lists(st.sampled_from(["push", "pop"]), max_size=40))
    def test_recorder_stack_never_corrupts(ops):
        rec = S.SpanRecorder()
        open_idx = []
        for op in ops:
            if op == "push":
                open_idx.append(rec.begin("s"))
            elif open_idx:
                rec.end(open_idx.pop())
        while open_idx:
            rec.end(open_idx.pop())
        assert rec._stack() == []
        assert all(sp.dur_ns >= 0 for sp in rec.spans)
        paths = S.span_paths(rec.spans)
        for sp, path in zip(rec.spans, paths):
            assert path.count("/") == sp.depth


# ----------------------------------------------------------- trace export

def test_chrome_trace_schema():
    with S.SpanRecorder() as rec:
        with S.span("outer", step=1):
            with S.span("inner"):
                pass
    doc = rec.to_chrome_trace(process_name="testproc")
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = events[0]
    assert meta["ph"] == "M" and meta["name"] == "process_name"
    assert meta["args"]["name"] == "testproc"
    for ev in events[1:]:
        assert ev["ph"] == "X"                    # complete events
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert isinstance(ev["dur"], float) and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    assert events[1]["args"] == {"step": 1}
    json.dumps(doc)                               # JSON-serialisable


def test_recorder_save_writes_loadable_trace(tmp_path):
    with S.SpanRecorder() as rec:
        with S.span("x"):
            pass
    path = rec.save(str(tmp_path / "sub" / "trace.json"))
    doc = json.load(open(path))
    assert doc["traceEvents"][1]["name"] == "x"


def test_to_records_roundtrip_through_report(tmp_path):
    from repro.obs import report as RPT
    with S.SpanRecorder() as rec:
        with S.span("step", step=0):
            with S.span("phase"):
                pass
    recs = rec.to_records()
    assert [r["path"] for r in recs] == ["step", "step/phase"]
    assert all(r["name"] == "span" for r in recs)
    assert recs[0]["step"] == 0
    path = tmp_path / "spans.jsonl"
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    doc = RPT.report([str(path)], trace_out=str(tmp_path / "tr.json"))
    assert set(doc["groups"]["span"]["paths"]) == {"step", "step/phase"}
    tr = json.load(open(tmp_path / "tr.json"))
    assert any(e.get("name") == "phase" for e in tr["traceEvents"])


# ------------------------------------------------------- zero-cost claims

def test_disabled_spans_do_not_enter_traced_code():
    """The traced train-step jaxpr is byte-identical whether the spans
    module exists or not: spans are host-side only."""
    from repro.configs.base import ModelConfig
    from repro.training.train_step import (TrainConfig, abstract_train_state,
                                           make_train_step)
    cfg = ModelConfig(n_layers=1, d_model=16, n_heads=2, n_kv_heads=1,
                      head_dim=8, d_ff=32, vocab=32,
                      param_dtype="float32", compute_dtype="float32")
    tc = TrainConfig(T=4, memory_mode="exact", remat=False, ce_chunks=1)
    state = abstract_train_state(cfg, tc, 2)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1, 8), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 1, 8), jnp.int32)}
    step = make_train_step(cfg, tc, 2)
    base = str(jax.make_jaxpr(step)(state, batch))
    with S.SpanRecorder():
        with S.span("around-trace"):
            inside = str(jax.make_jaxpr(step)(state, batch))
    assert inside == base


def test_loop_run_jaxpr_unchanged_by_recorder():
    """core.loop's trace_scope tags are pure metadata and its host spans
    never enter the scan: same jaxpr with and without a recorder."""
    from repro.core import graph as G, loop
    from repro.core.frodo import FrodoConfig, frodo

    def obj(x, i):
        return 0.5 * jnp.sum(x ** 2) + 0.1 * x[0] * i

    W = G.xiao_boyd_weights(G.complete(3))
    x0 = jnp.ones((3, 2), jnp.float32)
    opt = frodo(FrodoConfig(alpha=0.1, beta=0.05, lam=0.15, T=8))

    def traced(x):
        return loop.run_jax(obj, x, opt, W, 5)[1]

    base = str(jax.make_jaxpr(traced)(x0))
    with S.SpanRecorder():
        inside = str(jax.make_jaxpr(traced)(x0))
    assert inside == base


def test_noop_span_overhead_is_allocation_free():
    """With no recorder, a span keeps nothing once it closes: no row, no
    stack entry, no memory that outlives the call."""
    import tracemalloc

    def spans(n):
        for i in range(n):
            with S.span(f"name{i % 8}", step=i):
                pass

    spans(100)                                    # warm caches
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        spans(10_000)
        kept = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert kept < 4096, kept
    assert S.get_recorder() is None


# ------------------------------------------------------ driver integration

def test_loop_run_emits_host_spans():
    from repro.core import graph as G, loop
    from repro.core.frodo import FrodoConfig, frodo

    def obj(x, i):
        return 0.5 * jnp.sum(x ** 2) * (1.0 + 0.0 * i)

    W = G.xiao_boyd_weights(G.complete(3))
    x0 = jnp.ones((3, 2), jnp.float32)
    opt = frodo(FrodoConfig(alpha=0.1, beta=0.05, lam=0.15, T=8))
    with S.SpanRecorder() as rec:
        loop.run(obj, x0, opt, W, 3)
    paths = S.span_paths(rec.spans)
    assert paths == ["loop.run", "loop.run/loop.execute",
                     "loop.run/loop.drain"]
    agg = S.aggregate(rec.spans)
    assert agg["loop.run"].total_ms >= agg["loop.run/loop.execute"].total_ms


def test_threaded_spans_attribute_to_own_stacks():
    import threading
    rec = S.SpanRecorder()
    prev = S.set_recorder(rec)
    gate = threading.Barrier(3)   # keep all threads alive concurrently so
    try:                          # thread idents cannot be recycled
        def work(tag):
            gate.wait(timeout=10)
            with S.span(f"outer-{tag}"):
                with S.span(f"inner-{tag}"):
                    pass
        ts = [threading.Thread(target=work, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        S.set_recorder(prev)
    paths = S.span_paths(rec.spans)
    # every inner span nests under its own thread's outer span
    inners = [p for p in paths if "inner" in p]
    assert len(inners) == 3
    for p in inners:
        tag = p[-1]
        assert p == f"outer-{tag}/inner-{tag}"
    tids = {sp.tid for sp in rec.spans}
    assert len(tids) == 3


# -------------------------------------------------------------- report CLI

def test_report_phase_breakdown_and_trace(tmp_path):
    from repro.obs import report as RPT
    rows = []
    for i in range(6):
        rows.append({"name": "serve.step", "step": i,
                     "step_time_ms": 10.0,
                     "phase_prefill_ms": 6.0, "phase_decode_ms": 3.0,
                     "phase_admission_ms": 1.0})
    path = tmp_path / "steps.jsonl"
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    out = RPT.report([str(path)], top=2,
                     trace_out=str(tmp_path / "trace.json"))
    grp = out["groups"]["serve.step"]
    assert grp["n_steps"] == 6
    assert grp["coverage"] == pytest.approx(1.0)
    assert grp["min_step_coverage"] == pytest.approx(1.0)
    assert grp["phases"]["phase_prefill_ms"]["pct_of_step"] == \
        pytest.approx(0.6)
    assert len(grp["slowest"]) == 2
    tr = json.load(open(tmp_path / "trace.json"))
    names = [e.get("name") for e in tr["traceEvents"]]
    assert "serve.step" in names and "prefill" in names
    # phases of one step tile sequentially inside the step event
    phase_evs = [e for e in tr["traceEvents"] if e.get("cat") == "phase"]
    step_evs = [e for e in tr["traceEvents"] if e.get("cat") == "step"]
    assert len(phase_evs) == 18 and len(step_evs) == 6
    assert step_evs[1]["ts"] == pytest.approx(step_evs[0]["ts"]
                                              + step_evs[0]["dur"])


def test_report_cli_main(tmp_path, capsys):
    from repro.obs import report as RPT
    path = tmp_path / "m.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"name": "serve.step", "step": 0,
                            "step_time_ms": 5.0,
                            "phase_decode_ms": 5.0}) + "\n")
    assert RPT.main([str(path), "--top", "1"]) == 0
    out = capsys.readouterr().out
    assert "phase coverage" in out and "decode" in out
    assert RPT.main([str(tmp_path / "missing.jsonl")]) == 2


# -------------------------------------------------- regress phase bands

def test_regress_phase_columns_are_timing_metrics():
    from repro.obs import regress as R
    assert R.is_timing_metric("step_time_ms")
    assert R.is_timing_metric("phase_decode_ms")
    assert R.is_timing_metric("phase_admission_ms")
    assert not R.is_timing_metric("consensus_error")
    assert not R.is_timing_metric("phase_count")      # no _ms suffix
    rows = [{"exp": "t", "variant": "a", "step": s, "loss": 1.0 / (s + 1),
             "step_time_ms": 10.0, "phase_decode_ms": 8.0,
             "phase_admission_ms": 2.0} for s in range(5)]
    doc = R.make_baseline(rows, meta={"exp": "t"})
    entry = doc["series"]["exp=t/variant=a"]
    assert set(entry["timing"]) == {"step_time_ms", "phase_decode_ms",
                                    "phase_admission_ms"}
    assert set(entry["metrics"]) == {"loss"}
    # a regression confined to one phase trips its own band
    slow = [dict(r, phase_decode_ms=100.0) for r in rows]
    diffs = R.compare_to_baseline(doc, slow, R.Tolerance(timing_ratio=5.0))
    failed = {d.metric for d in diffs if not d.passed}
    assert failed == {"phase_decode_ms"}


def test_regress_timing_floor_skips_noise_phases():
    from repro.obs import regress as R
    tol = R.Tolerance(timing_ratio=2.0, timing_floor_ms=0.05)
    tiny = R.timing_percentiles(np.full(20, 0.01))    # 10 us phase
    d = R.compare_timing("g", "phase_telemetry_ms", tiny,
                         np.full(20, 0.04), tol)      # 4x slower but tiny
    assert d.passed and "floor" in d.detail
    big = R.timing_percentiles(np.full(20, 1.0))
    assert not R.compare_timing("g", "phase_decode_ms", big,
                                np.full(20, 3.0), tol).passed
