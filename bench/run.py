"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(weights from the seed, compilation or compile-cache loads, the first
steps), a window of ``--seconds`` of training, and the comparison with the
float32 reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window), ``device`` and, traced, ``breakdown``; ``checks``
comes last with each compared number and its limit, which also end
standard error.  Without a TPU, with fewer chips than the cell needs or
with a device missing from ``harness/peaks.py``, it exits non-zero and
prints no result.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".jax_cache"


def use_cache() -> None:
    """A fixed directory inside the checkout, whatever the environment
    says, so that two checkouts never share compiled programs.  It is also
    put in ``$JAX_COMPILATION_CACHE_DIR``, where the program's own entry
    points (``launch/compile_cache.py``) look for theirs."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    try:
        use_cache()
        from harness import peaks, runner
        result, checks, info = runner.run(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            T_START)
    except (runner.NoAccelerator, peaks.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except (ImportError, FileNotFoundError, KeyError) as e:
        print(f"bench: cannot run {args.workload!r}: {e!r}", file=sys.stderr)
        return 4
    print(f"bench: {json.dumps(info)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) at "
              f"{c['at']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
