"""The reduction from trace to metrics: on a small synthetic record whose
answers are known by hand, and on small records cut from real traces of
the cells (``data/``: a few steps each)."""
import glob
import gzip
import json
from pathlib import Path

import pytest

from harness import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def read(path: str) -> dict:
    """A record as ``trace_reduce.load`` returns it, kept as gzip JSON."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_intervals():
    u = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert tr.length(u) == 7
    assert tr.minus(u, tr.union([(2, 6)])) == 2 + 3
    assert tr.gaps(u, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert tr.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_op_names():
    assert tr.op_name("%fusion.612 = (bf16[2]{0}) fusion(x), kind=kLoop") \
        == "fusion.612"
    assert tr.op_kind("broadcast.58.clone2") == "broadcast"
    assert tr.is_collective("all-reduce-start.3")
    assert not tr.is_collective("fusion.7")


def synthetic():
    # window 0..100; two steps; chip 0 computes 10-40 and 55-85 with an
    # all-reduce 35-50 (exposed 40-50) in a while loop 10-50; chip 1 idle
    # but for one op.
    ops0 = [["while.1", 10, 50], ["fusion.1", 10, 40],
            ["all-reduce-start.2", 35, 50], ["fusion.1", 55, 85]]
    ops1 = [["convolution.4", 20, 30]]
    return {"host": [["bench.window", 0, 100], ["train", 5, 8],
                     ["train", 51, 53], ["bench.data", 50, 51],
                     ["bench.data", 86, 95]],
            "chips": {"/device:TPU:0": {"ops": ops0, "modules": [
                ["jit_step", 10, 50], ["jit_step", 55, 85]]},
                "/device:TPU:1": {"ops": ops1, "modules": []}}}


def test_reduce_synthetic():
    r = tr.reduce(synthetic())
    assert r["window_ns"] == 100 and r["steps"] == 2
    c0, c1 = r["chips"]
    assert c0["busy_ns"] == 40 + 30 and c1["busy_ns"] == 10
    assert c0["collective_ns"] == 15 and c0["exposed_ns"] == 10
    assert c0["module_gaps_ns"] == [5]
    assert r["busy_ns"] == 40
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion.1" and "while.1" not in names
    gaps = dict((round(s * 1e9), label) for label, s in r["idle_gaps"])
    # chip 0 idles 50-55: data 50-51, train 51-53; and 85-100: data 86-95
    assert gaps[5] == "train 40% other host 40% bench.data 20%"
    assert gaps[15] == "bench.data 60% other host 40%"


def test_metric_readers_on_synthetic():
    from harness import spec
    import tiny

    cell = spec.Cell(tiny.ROOT, "danube-1chip.seq2048")
    run = {"trace": tr.reduce(synthetic()), "steps": 2,
           "tokens_per_step": 8192, "window_s": 1e-7, "chips": 4,
           "peaks": {"bf16_flops": 197e12}, "flops_per_token": 1.0}
    got = {k: v["value"] for k, v in cell.read_metrics("per_layer",
                                                         run).items()}
    # the collective readers wait for a cell across chips (PERF.md)
    for name in ("collective_ms", "collective_exposed_ms"):
        got[name] = cell.reader(name)(run)
    assert got["device_idle_share"] == pytest.approx(60.0)
    assert got["host_gap_ms"] == pytest.approx(5e-6)
    assert got["collective_ms"] == pytest.approx(15 / 2 / 2 * 1e-6)
    assert got["collective_exposed_ms"] == pytest.approx(10 / 2 / 2 * 1e-6)


@pytest.mark.parametrize("path", sorted(
    p for p in glob.glob(str(DATA / "*.json.gz")) if ".expect." not in p))
def test_reduce_recorded(path):
    r = tr.reduce(read(path))
    expect = read(path.replace(".json.gz", ".expect.json.gz"))
    assert r["steps"] == expect["steps"]
    for key in ("window_ns", "busy_ns"):
        assert r[key] == pytest.approx(expect[key], rel=1e-9)
    for c, e in zip(r["chips"], expect["chips"]):
        for key in ("busy_ns", "collective_ns", "exposed_ns"):
            assert c[key] == pytest.approx(e[key], rel=1e-9)
    assert 0 < r["busy_ns"] <= r["window_ns"]
    # a second witness: the ops run inside the step program's executions,
    # which the trace records on a line of their own
    rec = read(path)
    lo, hi = tr.window_of(rec)
    for c in r["chips"]:
        mods = tr.clip([(s, e) for _, s, e in rec["chips"][c["plane"]]
                        ["modules"]], lo, hi)
        assert 0.9 * tr.length(mods) <= c["busy_ns"] <= tr.length(mods)
        assert c["exposed_ns"] <= c["collective_ns"] <= c["busy_ns"]
