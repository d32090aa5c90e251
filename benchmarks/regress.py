"""Golden-run trajectory regression driver (see src/repro/obs/regress.py).

Records seeded, reduced-scale runs of the paper experiments as baselines,
then diffs later runs against them — the CI gate that keeps convergence
curves and step times honest across PRs:

    python benchmarks/regress.py --record   # refresh benchmarks/baselines/
    python benchmarks/regress.py --check    # diff current tree; exit 1 on drift

``--check`` replays each experiment with the seed/steps stored in the
baseline's ``meta`` block (CLI flags override), so a plain ``--check``
always compares like for like.  Convergence trajectories are compared
pointwise with relative+absolute tolerances; ``step_time_ms`` gets a
one-sided percentile band (``--timing-ratio``, generous by default because
CI runners are noisy).  Intentional perf/convergence changes re-record:
run ``--record``, eyeball the baseline diff, and commit it with the PR.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import os as _os
import sys as _sys

_ROOT = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _ROOT)                       # for benchmarks.* imports
_sys.path.insert(0, _os.path.join(_ROOT, "src"))

from repro.obs import regress as R

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE_DIR = os.path.join(HERE, "baselines")

# reduced-scale defaults: small enough for CI, long enough that the
# convergence dynamics (memory ramp-up over T steps, consensus decay) show
DEFAULT_STEPS = {"exp1": 150, "exp2": 40, "exp3": 400, "train": 12,
                 "serve": 8}

#: trainer sink counters that are pure wall-clock (monotone / machine
#: dependent) — dropped from the train baseline; step_time_ms and the
#: per-phase phase_*_ms columns stay and are compared as percentile bands
#: like every other timing key
TRAIN_VOLATILE_KEYS = ("wall_s", "throughput_items_per_s")


def run_exp1(jsonl_path: str, seed: int, steps: int) -> None:
    from benchmarks.exp1_quadratic import write_metrics_jsonl
    del seed  # exp1 telemetry is a fixed representative point: no RNG
    write_metrics_jsonl(jsonl_path, steps=steps)


def run_exp2(jsonl_path: str, seed: int, steps: int) -> None:
    from benchmarks.exp2_federated import run_experiment
    run_experiment(steps=steps, n_seeds=1, out=None,
                   metrics_out=jsonl_path, seed=seed)


def run_exp3(jsonl_path: str, seed: int, steps: int) -> None:
    """Fault-injection sweep (benchmarks/exp3_faults.py) at reduced scale:
    ``steps`` drives the quadratic arm; the federated arm and the recorded
    trajectory window scale down with it."""
    from benchmarks.exp3_faults import run_experiment
    run_experiment(seed=seed, quad_steps=steps, fed_steps=max(steps // 8, 10),
                   out=None, metrics_out=jsonl_path,
                   metrics_steps=min(steps, 60))


def run_train(jsonl_path: str, seed: int, steps: int) -> None:
    """Smoke-scale ``launch.train --metrics-out`` golden run.  The trainer
    sink has no group keys and mixes wall-clock counters into every record,
    so the stream is rewritten: volatile counters out, series identity in."""
    from repro.launch.train import run_training
    raw = jsonl_path + ".raw"
    run_training(arch="h2o-danube-1.8b", smoke=True, steps=steps,
                 agents=2, metrics_out=raw, collect_metrics=True, seed=seed)
    with open(raw) as src, open(jsonl_path, "w") as dst:
        for line in src:
            rec = json.loads(line)
            for k in TRAIN_VOLATILE_KEYS:
                rec.pop(k, None)
            rec.update(exp="launch_train", name="h2o-danube-1.8b-smoke",
                       seed=seed)
            dst.write(json.dumps(rec) + "\n")
    os.remove(raw)


def run_serve(jsonl_path: str, seed: int, steps: int) -> None:
    """Seeded Poisson-arrival serving trace (benchmarks/serve_bench.py):
    ``steps`` is the number of synthetic requests.  Queue/occupancy
    counters, TTFT in scheduler steps, and greedy token checksums are all
    deterministic; wall-clock keys are stripped by the bench."""
    from benchmarks.serve_bench import run_bench
    run_bench(jsonl_path, seed=seed, n_requests=steps)


RUNNERS = {"exp1": run_exp1, "exp2": run_exp2, "exp3": run_exp3,
           "train": run_train, "serve": run_serve}


def baseline_path(baseline_dir: str, exp: str) -> str:
    return os.path.join(baseline_dir, f"{exp}.json")


def record(exp: str, baseline_dir: str, seed: int, steps: int) -> str:
    with tempfile.TemporaryDirectory() as tmp:
        jsonl = os.path.join(tmp, f"{exp}.jsonl")
        RUNNERS[exp](jsonl, seed=seed, steps=steps)
        base = R.make_baseline(jsonl, meta={"exp": exp, "seed": seed,
                                            "steps": steps})
    return R.write_baseline(baseline_path(baseline_dir, exp), base)


def check(exp: str, baseline_dir: str, tol: R.Tolerance,
          seed: int | None, steps: int | None,
          include_timing: bool) -> list:
    path = baseline_path(baseline_dir, exp)
    if not os.path.exists(path):
        return [R.MetricDiff(f"exp={exp}", "*", False, "structure",
                             f"no baseline at {path}; run --record first")]
    base = R.load_baseline(path)
    meta = base.get("meta", {})
    seed = meta.get("seed", 0) if seed is None else seed
    steps = meta.get("steps", DEFAULT_STEPS[exp]) if steps is None else steps
    with tempfile.TemporaryDirectory() as tmp:
        jsonl = os.path.join(tmp, f"{exp}.jsonl")
        RUNNERS[exp](jsonl, seed=seed, steps=steps)
        return R.compare_to_baseline(base, jsonl, tol,
                                     include_timing=include_timing)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--record", action="store_true",
                      help="write fresh baselines (then commit them)")
    mode.add_argument("--check", action="store_true",
                      help="diff against baselines; exit 1 on drift")
    ap.add_argument("--exp", nargs="+", choices=sorted(RUNNERS),
                    default=sorted(RUNNERS), help="experiments to cover")
    ap.add_argument("--baseline-dir", default=DEFAULT_BASELINE_DIR)
    ap.add_argument("--seed", type=int, default=None,
                    help="base seed (default: 0 on record, baseline meta "
                         "on check)")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps per experiment (default: reduced-scale "
                         "presets on record, baseline meta on check)")
    ap.add_argument("--rtol", type=float, default=0.05,
                    help="pointwise relative tolerance on trajectories")
    ap.add_argument("--atol", type=float, default=1e-6,
                    help="absolute floor for decayed-to-noise metrics")
    ap.add_argument("--max-violation-frac", type=float, default=0.02,
                    help="fraction of points allowed outside tolerance")
    ap.add_argument("--timing-ratio", type=float, default=10.0,
                    help="fail when a timing metric's p50 (step_time_ms "
                         "or any phase_*_ms) exceeds baseline p50 by this "
                         "factor; CI passes 5 (see docs/observability.md)")
    ap.add_argument("--no-timing", action="store_true",
                    help="skip the step_time_ms band (trajectories only)")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="also write the per-metric report as JSON")
    args = ap.parse_args()

    if args.record:
        seed = 0 if args.seed is None else args.seed
        for exp in args.exp:
            steps = args.steps or DEFAULT_STEPS[exp]
            path = record(exp, args.baseline_dir, seed, steps)
            print(f"recorded {exp} baseline (seed={seed}, steps={steps}) "
                  f"-> {path}")
        return 0

    tol = R.Tolerance(rtol=args.rtol, atol=args.atol,
                      max_violation_frac=args.max_violation_frac,
                      timing_ratio=args.timing_ratio)
    diffs = []
    for exp in args.exp:
        diffs += check(exp, args.baseline_dir, tol, args.seed, args.steps,
                       include_timing=not args.no_timing)
    print(R.format_report(diffs))
    if args.report:
        os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(R.report_json(diffs), f, indent=1)
        print(f"report -> {args.report}")
    return 0 if all(d.passed for d in diffs) else 1


if __name__ == "__main__":
    sys.exit(main())
