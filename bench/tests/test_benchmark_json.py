"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to a file under ``bench/``."""
import json
import re

import tiny

B = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    cells = len(B["workloads"])
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, cells // 2)


def test_entries():
    names = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["why"])
        assert c["file"].startswith("bench/")
        f = json.loads((tiny.ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k) and f["published"][k] != f["model"][k]
        names.add(c["name"])
    pairs = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
        assert w["config"] in names
        assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert (tiny.BENCH / "limits" / f"{w['name']}.json").exists()
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(B["workloads"])
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and LINE.match(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").exists()
    assert len(tiny.ROOT.joinpath("BENCHMARK.json").read_bytes()) <= 65536


def test_every_cell_reports_enough():
    for w in B["workloads"]:
        def on(m):
            return "workloads" not in m or w["name"] in m["workloads"]
        e2e = [m["name"] for m in B["end_to_end"] if on(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(on(m) for m in B["per_layer"])
