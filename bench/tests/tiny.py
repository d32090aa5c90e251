"""A tiny copy of the benchmark for tests on the CPU: the smoke-sized
h2o-danube-1.8b (2 layers, d_model 256, 8/2 heads, window 64) with two
agents, added as new files to a copy of ``BENCHMARK.json`` and ``bench/``."""
import functools
import json
import os
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CELL = "danube-smoke.seq128"

MODEL = {"arch": "h2o-danube-1.8b", "family": "dense", "n_layers": 2,
         "d_model": 256, "n_heads": 8, "n_kv_heads": 2, "head_dim": 32,
         "d_ff": 512, "vocab": 512, "window": 64, "rope_theta": 10000.0,
         "norm_eps": 1e-06, "activation": "silu", "gated_mlp": True,
         "tie_embeddings": False, "param_dtype": "bfloat16",
         "compute_dtype": "bfloat16"}


@functools.cache
def model(code: str = "dense_swa"):
    """The model module ``bench/models/<code>.py``, as the harness loads
    it."""
    from harness import spec
    return spec.load(BENCH / "models" / f"{code}.py", f"bench_model_{code}")


def make_copy(dest: Path, limits=None, chips: int = 1) -> Path:
    """``dest`` gets BENCHMARK.json, bench/ and a link to src/, plus the
    smoke configuration, a 128-token traffic mix and a cell of them: two
    agents on one device, or with ``chips=4`` four agents over a (4,1)
    mesh."""
    dest = Path(dest)
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", dest / "src")
    cfg = json.loads((BENCH / "configs" / "h2o-danube-1.8b.1chip.json")
                     .read_text())
    cfg.update(name="h2o-danube-1.8b.smoke", model=dict(MODEL))
    cfg["trainer"].update(smoke=True, layers=0,
                          agents=2 if chips == 1 else chips)
    cfg["chips"] = chips
    cfg["mesh"] = None if chips == 1 else {"data": chips, "model": 1}
    (dest / "bench/configs/h2o-danube-1.8b.smoke.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((BENCH / "traffic" / "seq2048.json").read_text())
    traffic.update(name="seq128", seq_len=128, chunk_steps=2)
    (dest / "bench/traffic/seq128.json").write_text(json.dumps(traffic))
    (dest / f"bench/limits/{CELL}.json").write_text(json.dumps(
        limits or json.loads((BENCH / "limits" /
                              "danube-1chip.seq2048.json").read_text())))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "h2o-danube-1.8b.smoke", "source": "arXiv:2401.16818",
        "file": "bench/configs/h2o-danube-1.8b.smoke.json",
        "reduced": ["n_layers"], "why": "CPU test size"})
    bench["workloads"].append({
        "name": CELL, "config": "h2o-danube-1.8b.smoke",
        "traffic": "seq128", "chips": chips, "why": "CPU test size"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest
