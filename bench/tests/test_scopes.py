"""The split of the step's device time by name scope and of the device's
idle time by host span (``harness/scopes.py``): on a synthetic record whose
answers are worked out by hand, and on the recorded traces in ``data/``
(``danube-1chip-scopes``: three steps of the one-chip cell on a TPU v5e,
cut from a run of ``bench/split.py``)."""
import glob
import gzip
import json
import re

import pytest

from harness import scopes as sc
from harness import trace_reduce as tr
from test_trace_reduce import DATA, read

FWD = "jit(train_step)/vmap(transpose(jvp(train.fwd_bwd)))/dot_general"


def test_scope_of():
    assert sc.scope_of(FWD) == "train.fwd_bwd"
    assert sc.scope_of("jit(train_step)/frodo.update/"
                       "pallas.frodo_expsum_update/add") == "frodo.update"
    assert sc.scope_of("jit(train_step)/consensus.mix_uniform/reduce_sum") \
        == "consensus.mix_uniform"
    # the names of the step's arguments are no scopes
    assert sc.scope_of("state.params['blocks']['w']") == "unscoped"
    assert sc.scope_of("state.step") == "unscoped"
    assert sc.scope_of("jit(train_step)/add") == "unscoped"
    assert sc.scope_of("") == "unscoped"


def test_load_scopes(tmp_path):
    events = [{"ph": "X", "name": "fusion.1", "args": {"tf_op": FWD + ":"}},
              {"ph": "X", "name": "copy.2", "args": {"source": "x.py:1"}},
              {"ph": "X", "name": "fusion.1", "args": {"tf_op": "other"}}]
    path = tmp_path / "h.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f, separators=(",", ":"))
    assert sc.load_scopes(str(path)) == {"fusion.1": FWD, "copy.2": ""}


def test_innermost():
    spans = [["outer", 0, 10], ["inner", 2, 4], ["twin", 0, 1],
             ["late", 8, 12]]
    assert sc.innermost(spans, -1, 11) == [
        [-1, 0, "other host"], [0, 1, "twin"], [1, 2, "outer"],
        [2, 4, "inner"], [4, 8, "outer"], [8, 11, "late"]]


def synthetic():
    """Window 0..100, two steps.  Host: the benchmark's annotations and the
    program's spans (a collection inside the first ``train.log``).  Chip 0
    runs each step's forward/backward, update, mix, an unscoped copy and an
    op the trace's JSON does not name, inside a loop; chip 1 one op."""
    ops0 = [["while.1", 12, 39], ["fusion.1", 12, 30], ["fusion.2", 30, 36],
            ["convert_reduce_fusion", 36, 38], ["copy.1", 38, 39],
            ["fusion.1", 62, 80], ["fusion.2", 80, 86],
            ["convert_reduce_fusion", 86, 88], ["copy.1", 88, 89],
            ["mystery.3", 89, 90]]
    return {
        "host": [["bench.window", 0, 100], ["train", 10, 14],
                 ["train", 60, 64], ["bench.data", 3, 6],
                 ["bench.data", 53, 56]],
        "spans": [["train.step", 2, 50], ["train.data", 2, 7],
                  ["train.device_step", 11, 13], ["train.metrics", 15, 40],
                  ["train.log", 40, 45], ["gc.gen2", 41, 44],
                  ["train.step", 52, 98], ["train.data", 52, 57],
                  ["train.device_step", 61, 63], ["train.metrics", 65, 90],
                  ["train.log", 90, 92]],
        "scopes": {"while.1": "jit(train_step)/while", "fusion.1": FWD,
                   "fusion.2": "jit(train_step)/frodo.update/add",
                   "convert_reduce_fusion":
                       "jit(train_step)/consensus.mix_uniform/reduce_sum",
                   "copy.1": ""},
        "chips": {"/device:TPU:0": {"ops": ops0, "modules": []},
                  "/device:TPU:1": {"ops": [["convolution.4", 20, 30]],
                                    "modules": []}}}


def test_reduce_synthetic():
    r = sc.reduce(synthetic())
    assert r["steps"] == 2
    c0, c1 = r["chips"]
    assert c0["scope_ns"] == {"train.fwd_bwd": 36, "frodo.update": 12,
                              "consensus.mix_uniform": 4, "unscoped": 2,
                              "unseen": 1}
    assert c1["scope_ns"] == {"unseen": 10}
    # chip 0 idles 0-12, 39-62 and 90-100; each instant goes to the
    # innermost span: 2-3 is train.data (it and train.step start at 2),
    # 41-44 the collection, 50-52 and 98-100 no span
    assert c0["idle_by_span_ns"] == {
        "other host": 6, "train.data": 4, "bench.data": 6, "train.step": 17,
        "train": 2, "train.device_step": 2, "train.metrics": 1,
        "train.log": 4, "gc.gen2": 3}
    assert sum(c1["idle_by_span_ns"].values()) == 90
    assert c1["idle_by_span_ns"]["train.metrics"] == 5 + 10 + 25
    gaps = {round(d * 1e9): label for label, d in r["idle_gaps"]}
    assert sorted(gaps) == [10, 12, 20, 23, 70]
    assert gaps[10] == "train.step 60% train.log 20% other host 20%"


def test_per_step_ms_synthetic():
    got = sc.per_step_ms(sc.reduce(synthetic()))
    # ns summed over the chips, over 2 chips and 2 steps, in ms
    assert got["fwd_bwd_ms"] == pytest.approx(36 / 4 * 1e-6)
    assert got["update_ms"] == pytest.approx(12 / 4 * 1e-6)
    assert got["mix_ms"] == pytest.approx(4 / 4 * 1e-6)
    assert got["unscoped_ms"] == pytest.approx(2 / 4 * 1e-6)
    assert got["fetch_idle_ms"] == pytest.approx((1 + 40) / 4 * 1e-6)
    assert got["scope_ms"]["unseen"] == pytest.approx(11 / 4 * 1e-6)
    assert got["steps"] == 2


def test_trace_reduce_carries_the_split_and_the_readers_read_it():
    """``trace_reduce.reduce`` adds each chip's ``scope_ns`` and
    ``idle_by_span_ns`` beside its own keys, and the per-layer readers take
    the per-step numbers from them; a trace without the step's scopes and
    spans gives them nothing to read."""
    from harness import spec
    import tiny

    rec = synthetic()
    base, split = tr.reduce(rec), sc.reduce(rec)
    for c, s in zip(base["chips"], split["chips"]):
        assert c["scope_ns"] == s["scope_ns"]
        assert c["idle_by_span_ns"] == s["idle_by_span_ns"]
    cell = spec.Cell(tiny.ROOT, "danube-1chip.seq2048")
    names = ("fwd_bwd_ms", "update_ms", "mix_ms", "unscoped_ms",
             "fetch_idle_ms")
    want = sc.per_step_ms(split)
    for name in names:
        assert cell.reader(name)({"trace": base}) == pytest.approx(
            want[name], rel=1e-12)
        assert cell.reader(name)({"trace": None}) is None
    bare = dict(rec, scopes={}, spans=[])
    got = {n: cell.reader(n)({"trace": tr.reduce(bare)}) for n in names}
    assert got == dict.fromkeys(names)


def test_without_program_scopes_and_spans():
    """A record of a program without the step's scopes and the trainer's
    spans: their numbers are None, and the gaps keep trace_reduce's
    labels."""
    rec = synthetic()
    del rec["spans"]
    rec["scopes"] = {k: v for k, v in rec["scopes"].items()
                     if "consensus" in v}
    got = sc.per_step_ms(sc.reduce(rec))
    assert got["fwd_bwd_ms"] is None and got["update_ms"] is None
    assert got["unscoped_ms"] is None and got["fetch_idle_ms"] is None
    assert got["mix_ms"] == pytest.approx(1e-6)
    assert [g for g, _ in sc.reduce(rec)["idle_gaps"]] == \
        [g for g, _ in tr.reduce(rec)["idle_gaps"]]


@pytest.mark.parametrize("path", sorted(
    p for p in glob.glob(str(DATA / "*.json.gz")) if ".expect." not in p))
def test_recorded(path):
    rec = read(path)
    r, base = sc.reduce(rec), tr.reduce(rec)
    assert r["steps"] == base["steps"]
    for c, b in zip(r["chips"], base["chips"]):
        # every operation counts to one scope, and the operations (loops
        # left out) fill the busy time; idle time counts to one span
        assert sum(c["scope_ns"].values()) == pytest.approx(b["busy_ns"],
                                                            rel=0.01)
        assert sum(c["idle_by_span_ns"].values()) == pytest.approx(
            base["window_ns"] - b["busy_ns"], rel=1e-9)
    if "spans" not in rec:
        assert r["idle_gaps"] == base["idle_gaps"]
        return
    # a trace of the program with the step's scopes and the trainer's spans
    got = sc.per_step_ms(r)
    expect = read(path.replace(".json.gz", ".expect.json.gz"))["split"]
    parts = ("fwd_bwd_ms", "update_ms", "mix_ms", "unscoped_ms")
    for key in parts + ("fetch_idle_ms",):
        assert got[key] == pytest.approx(expect[key], rel=1e-9)
    busy_ms = base["busy_ns"] / base["steps"] * 1e-6
    assert sum(got[k] for k in parts) == pytest.approx(busy_ms, rel=0.02)
    assert got["unscoped_ms"] < 0.05 * busy_ms
    for label, _ in r["idle_gaps"]:
        other = re.search(r"other host (\d+)%", label)
        assert other is None or int(other.group(1)) < 25, label
