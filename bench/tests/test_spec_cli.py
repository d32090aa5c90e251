"""The harness finds every piece of a cell by name, so a later change adds
a configuration, a traffic mix or a metric reader as new files; and the
command refuses to run without a TPU or outside a full checkout."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import tiny
from harness import peaks, runner, spec

RUN = tiny.BENCH / "run.py"


def digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    base = tmp_path / "base"
    base.mkdir()
    shutil.copy(tiny.ROOT / "BENCHMARK.json", base)
    shutil.copytree(tiny.BENCH, base / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(base)
    new = tmp_path / "new"
    new.mkdir()
    tiny.make_copy(new)
    (new / "bench/metrics/tokens_per_chip.py").write_text(
        "def read(run):\n"
        "    return run['steps'] * run['tokens_per_step'] / run['chips']\n")
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tokens_per_chip", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": [tiny.CELL]})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))

    after = digests(new)
    changed = [p for p, h in before.items()
               if p != "BENCHMARK.json" and after.get(p) != h]
    assert changed == []                     # only additions
    added = sorted(p for p in set(after) - set(before)
                   if not p.startswith("src"))
    assert added == sorted([
        "bench/configs/h2o-danube-1.8b.smoke.json",
        f"bench/limits/{tiny.CELL}.json",
        "bench/metrics/tokens_per_chip.py",
        "bench/traffic/seq128.json"])

    cell = spec.Cell(new, tiny.CELL)
    assert cell.config["name"] == "h2o-danube-1.8b.smoke"
    assert cell.traffic["seq_len"] == 128
    assert "loss1" in cell.limits["limits"]
    got = cell.read_metrics("per_layer", {"steps": 10, "tokens_per_step": 256,
                                          "chips": 1, "trace": None})
    assert got == {"tokens_per_chip": {"value": 2560.0, "unit": "tokens"}}
    # the metric names no other cell, and the shipped cells still resolve
    other = spec.Cell(new, "danube-1chip.seq2048")
    assert "tokens_per_chip" not in [m["name"]
                                     for m in other.metrics("per_layer")]


def command(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "danube-1chip.seq2048", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = command(tiny.ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_in_a_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = command(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


class FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_a_device_missing_from_the_peak_table_is_an_error(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [FakeDevice("TPU v9x")])
    with pytest.raises(peaks.UnknownDevice):
        runner.devices_for(1, True)
    monkeypatch.setattr(jax, "devices",
                        lambda: [FakeDevice("TPU v5 lite")])
    assert len(runner.devices_for(1, True)) == 1
    with pytest.raises(runner.NoAccelerator):
        runner.devices_for(4, True)


def test_a_reader_sees_what_the_program_counts(tmp_path):
    """Readers added as new files get the program's ``obs.record`` records
    of set-up and the window, and the trainer's step records of the window:
    on the CPU the FrODO update takes jnp for every parameter, and with
    chunks of two steps and ``log_every`` 5 every step of the window is
    logged.  The sink set before the run is set again after it."""
    import time

    import numpy as np
    from repro import obs

    root = tiny.make_copy(tmp_path)
    (root / "bench/metrics/jnp_params.py").write_text(
        "def read(run):\n"
        "    got = {r['name']: r['value'] for r in run['records']}\n"
        "    assert got['frodo.fused_params'] == 0\n"
        "    return got['frodo.jnp_params']\n")
    (root / "bench/metrics/logged_steps.py").write_text(
        "def read(run):\n"
        "    return sum('loss' in h for h in run['history'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("jnp_params", "logged_steps"):
        bench["per_layer"].append({
            "name": name, "unit": "1", "better": "higher",
            "source": "program_counter", "layer": "train step",
            "moves": "train_tokens_per_s", "workloads": [tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    before = obs.NullSink()
    obs.set_sink(before)
    result, _, _ = runner.run(root, tiny.CELL, 2**31 + 9, 0.5, True,
                              time.perf_counter(),
                              require_accelerator=False)
    assert obs.get_sink() is before
    got = {k: v["value"] for k, v in result["metrics"].items()}
    n = sum(int(np.prod(e[0]))
            for e in tiny.model().leaf_shapes(tiny.MODEL).values())
    assert got == {"jnp_params": 2 * n, "logged_steps": result["attempted"]}
