"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by name:

* configuration: the ``file`` its ``configs`` entry names;
* the model the configuration brings: ``bench/models/<model_code>.py``,
  with ``model_code`` a key of the configuration file.  The module gives
  ``leaf_shapes(m)`` (``{path: (shape, fan_in)}`` of one agent, or
  ``(shape, fan_in, dtype)`` for a leaf kept in a dtype of its own),
  ``stacks(m)`` (``{prefix: layers}`` of each stacked layer group),
  ``train_flops_per_token(m, seq_len)``, ``check_model(cfg, m)`` (raises
  where the program's model differs from the file's ``model``) and
  ``agent_grad(p, tokens, labels, m, quant, half)`` (one agent's float32
  loss and gradients, layer by layer, for ``harness/reference.py``);
* traffic mix: ``bench/traffic/<traffic>.json``;
* the limits of a cell's comparison: ``bench/limits/<cell>.json``;
* a metric's reader: ``bench/metrics/<metric>.py``, whose ``read(run)``
  returns the number, or None where the run holds nothing to read.

A later change adds a cell, a configuration or a metric by adding such
files and entries; nothing here has to change.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


class Cell:
    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench_dir = self.root / self.bench["paths"][0]
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"({sorted(cells)})")
        self.name = name
        self.workload = cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        entry = configs[self.workload["config"]]
        self.config = json.loads((self.root / entry["file"]).read_text())
        code = self.config["model_code"]
        self.model = load(self.bench_dir / "models" / f"{code}.py",
                          f"bench_model_{code}")
        self.traffic = self._json("traffic", self.workload["traffic"])
        self.limits = self._json("limits", name)
        self.chips = int(self.workload["chips"])

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.bench_dir / kind / f"{name}.json").read_text())

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        return load(self.bench_dir / "metrics" / f"{metric}.py",
                    f"bench_metric_{metric}").read

    def read_metrics(self, kind: str, run: dict) -> dict:
        out = {}
        for m in self.metrics(kind):
            value = self.reader(m["name"])(run)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


def load(path: Path, name: str):
    """The module in the file ``path``, under ``name``."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
