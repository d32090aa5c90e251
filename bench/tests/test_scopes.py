"""The split of the step's device time by name scope, outermost and
chained, and of the device's idle time by host span
(``harness/scopes.py``): on literal op_name paths and a synthetic record
whose answers are worked out by hand, and on the recorded traces in
``data/`` (``danube-1chip-scopes``: three steps of the one-chip cell on a
TPU v5e, cut from a run of ``bench/split.py``)."""
import glob
import gzip
import json
import re

import pytest

from harness import scopes as sc
from harness import trace_reduce as tr
from test_trace_reduce import DATA, read

FWD = "jit(train_step)/vmap(transpose(jvp(train.fwd_bwd)))/dot_general"
KERNEL = ("jit(train_step)/frodo.update/pallas.frodo_expsum_apply/"
          "shard_map/pallas_call")


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


@pytest.mark.parametrize("path, chain", [
    (FWD, "train.fwd_bwd"),
    (KERNEL, "frodo.update/pallas.frodo_expsum_apply"),
    ("jit(train_step)/consensus.mix_uniform/reduce_sum",
     "consensus.mix_uniform"),
    # a scope that a transformation repeats further in counts once
    ("jit(train_step)/train.fwd_bwd/vmap(transpose(jvp(train.fwd_bwd)))/"
     "moe.experts/dot_general", "train.fwd_bwd/moe.experts"),
    ("jit(train_step)/vmap(transpose(jvp(train.fwd_bwd)))/"
     "vmap(transpose(jvp(moe.dispatch)))/train.fwd_bwd/gather",
     "train.fwd_bwd/moe.dispatch"),
    # remat: the recompute is nested in the scope it was made in
    ("jit(train_step)/train.fwd_bwd/vmap(transpose(jvp()))/while/body/"
     "closed_call/checkpoint/rematted_computation/mla.attn/"
     "...d,dhk->...hk/dot_general", "train.fwd_bwd/mla.attn"),
    ("jit(train_step)/train.fwd_bwd/checkpoint/train.fwd_bwd/"
     "rematted_computation/train.fwd_bwd/mul", "train.fwd_bwd"),
    # a fusion of two places (TPU v5e, danube-1chip-expsum): the first
    # path's chain, not both chained
    ("jit(train_step)/frodo.update/pallas.frodo_expsum_apply/transpose;"
     "jit(train_step)/train.fwd_bwd/vmap()/transpose",
     "frodo.update/pallas.frodo_expsum_apply"),
    ("jit(train_step)/transpose;jit(train_step)/train.fwd_bwd/mul",
     "train.fwd_bwd"),
    # the names of the step's arguments are no scopes
    ("state.params['blocks']['w']", "unscoped"),
    ("jit(train_step)/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_chain(path, chain):
    assert sc.scope_chain(path) == chain
    # the outermost scope leads the chain
    assert chain.split("/")[0] == sc.scope_of(path)


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
                   "fusion.2": KERNEL,
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
    assert c0["scope_path_ns"] == {
        "train.fwd_bwd": 36, "frodo.update/pallas.frodo_expsum_apply": 12,
        "consensus.mix_uniform": 4, "unscoped": 2, "unseen": 1}
    assert c1["scope_path_ns"] == {"unseen": 10}
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
    assert got["scope_path_ms"][
        "frodo.update/pallas.frodo_expsum_apply"] == got["update_ms"]
    assert got["steps"] == 2


def test_scope_ms_synthetic():
    """A scope at any depth of the chain, or a run of scopes; whole names
    only."""
    r = sc.reduce(synthetic())
    upd = sc.per_step_ms(r)["update_ms"]
    for name in ("frodo.update", "pallas.frodo_expsum_apply",
                 "frodo.update/pallas.frodo_expsum_apply"):
        assert sc.scope_ms(r, name) == pytest.approx(upd, rel=1e-12)
    assert sc.scope_ms(r, "train.fwd_bwd") == pytest.approx(36 / 4 * 1e-6)
    assert sc.scope_ms(r, "consensus.mix_uniform") == pytest.approx(1e-6)
    for name in ("pallas", "frodo", "pallas.frodo_expsum_apply/frodo.update",
                 "moe.experts"):
        assert sc.scope_ms(r, name) is None
    assert sc.scope_ms(dict(r, steps=0), "frodo.update") is None


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
        assert c["scope_path_ns"] == s["scope_path_ns"]
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
        # the chains split the same time; their outermost scopes give
        # scope_ns back
        assert sum(c["scope_path_ns"].values()) == pytest.approx(
            sum(c["scope_ns"].values()), rel=1e-12)
        outer = {}
        for k, v in c["scope_path_ns"].items():
            outer[k.split("/")[0]] = outer.get(k.split("/")[0], 0.0) + v
        assert outer == pytest.approx(c["scope_ns"], rel=1e-12)
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
    if "scope_path_ms" in expect:
        assert got["scope_path_ms"] == pytest.approx(expect["scope_path_ms"],
                                                     rel=1e-9)
    assert sc.scope_ms(r, "frodo.update") == pytest.approx(got["update_ms"],
                                                           rel=1e-12)
    assert sc.scope_ms(base, "train.fwd_bwd") == pytest.approx(
        got["fwd_bwd_ms"], rel=1e-12)
    busy_ms = base["busy_ns"] / base["steps"] * 1e-6
    assert sum(got[k] for k in parts) == pytest.approx(busy_ms, rel=0.02)
    assert got["unscoped_ms"] < 0.05 * busy_ms
    for label, _ in r["idle_gaps"]:
        other = re.search(r"other host (\d+)%", label)
        assert other is None or int(other.group(1)) < 25, label


def test_the_kernel_chain_holds_every_kernel_operation():
    """``danube-1chip-expsum`` (three steps of the one-chip cell with the
    fused update, TPU v5e): the time under the chain
    ``frodo.update/pallas.frodo_expsum_apply`` is that of every operation
    of kind ``frodo_expsum_apply`` in the record's op list, within 1 ms a
    step (the chain also holds the layout transposes fused into the
    kernel's calls, 0.35 ms), and it lies inside the update's."""
    rec = read(str(DATA / "danube-1chip-expsum.json.gz"))
    r = sc.reduce(rec)
    lo, hi = tr.window_of(rec)
    kernel = sum(min(e, hi) - max(s, lo) for c in rec["chips"].values()
                 for n, s, e in c["ops"]
                 if tr.op_kind(n) == "frodo_expsum_apply" and e > lo
                 and s < hi) / len(rec["chips"]) / r["steps"] * 1e-6
    chain = sc.scope_ms(r, "frodo.update/pallas.frodo_expsum_apply")
    assert kernel > 25.0
    assert kernel <= chain < kernel + 1.0
    assert chain == sc.scope_ms(r, "pallas.frodo_expsum_apply")
    assert chain < sc.per_step_ms(r)["update_ms"]


def hbm_run(config: str, model=None, **over):
    """A hand-made run of ``update_hbm_share``: two steps in which the
    update's scope takes 31.58 ms each, 29.5 of them in the kernel."""
    from harness import spec
    import tiny

    cfg = json.loads((tiny.BENCH / "configs" / f"{config}.json").read_text())
    trace = {"steps": 2, "chips": [{"scope_path_ns": {
        "frodo.update/pallas.frodo_expsum_apply": 59.0e6,
        "frodo.update": 4.16e6, "train.fwd_bwd": 244.2e6}}] * cfg["chips"]}
    run = {"trace": trace, "config": cfg, "chips": cfg["chips"],
           "model": model or spec.load(
               tiny.BENCH / "models" / f"{cfg['model_code']}.py", "m"),
           "peaks": {"hbm_bytes_per_s": 819e9}}
    run.update(over)
    return run


def test_update_hbm_share():
    """24 B a parameter of the chip (the clip's read of bfloat16 gradients,
    then the update's with four bfloat16 accumulators) over the whole update
    scope's time, over 819 GB/s; a float32 leaf moves 4 x 4 + 2 x 4 x 2 =
    32 B a parameter; without the clip each moves a gradient read less."""
    from harness import spec
    import tiny
    import toy_two_stack
    from test_models import TOY

    hbm = spec.load(tiny.BENCH / "metrics" / "update_hbm_share.py", "hbm")
    # 2 agents x 441.7 M parameters on one chip; 4 x 580.7 M over four
    for config, per_chip in (("h2o-danube-1.8b.1chip", 883_471_360),
                             ("h2o-danube-1.8b.4chip", 580_682_240)):
        run = hbm_run(config)
        assert hbm.update_bytes(run) == per_chip * 24
        assert hbm.read(run) == pytest.approx(
            100 * per_chip * 24 / 31.58e-3 / 819e9, rel=1e-12)
        run["config"]["grad_clip"] = 0.0
        assert hbm.update_bytes(run) == per_chip * 22
    # the toy: 45312 bfloat16 parameters and a float32 router of 512, two
    # agents on one chip
    toy = hbm_run("h2o-danube-1.8b.1chip", toy_two_stack)
    toy["config"]["model"] = dict(TOY)
    assert hbm.update_bytes(toy) == 2 * (45312 * 24 + 512 * 32)
    # nothing to read
    for over in ({"trace": None}, {"peaks": None},
                 {"trace": {"steps": 2, "chips": [{"scope_path_ns": {
                     "train.fwd_bwd": 1.0}}]}}):
        assert hbm.read(hbm_run("h2o-danube-1.8b.1chip", **over)) is None
    exact = hbm_run("h2o-danube-1.8b.1chip")
    exact["config"]["trainer"]["memory_mode"] = "exact"
    assert hbm.read(exact) is None
