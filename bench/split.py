"""One traced run of a cell, with the step's device time split by the
program's name scopes and the device's idle time by its host spans.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints that command's
result line, then one JSON object from ``harness/scopes.py``: per step,
``fwd_bwd_ms`` (``train.fwd_bwd``), ``update_ms`` (``frodo.update``),
``mix_ms`` (``consensus.*``), ``unscoped_ms``, ``fetch_idle_ms`` (device
idle while the host is in ``train.metrics``), every scope, chain of scopes
(``scope_path_ms``) and span by name, and the longest idle gaps labelled by
the innermost host span; with them ``expsum_kernel_share``, the share of
``update_ms`` under the scope ``pallas.frodo_expsum_apply``.  A program
without those scopes and spans gives None for their numbers.

``trace_reduce.load`` (which adds the scopes and spans to its record) is
wrapped for the run, so the trace is read where ``bench/run.py`` reads it,
before the run deletes it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    bench_run.use_cache()
    from harness import runner, scopes, trace_reduce

    reduced = {}
    load = trace_reduce.load

    def load_and_split(path):
        record = load(path)
        reduced.update(scopes.reduce(record))
        return record

    trace_reduce.load = load_and_split
    result, _, info = runner.run(bench_run.ROOT, args.workload, args.seed,
                                 args.seconds, True, T_START)
    print(f"bench: {json.dumps(info)}", file=sys.stderr, flush=True)
    print(json.dumps(result))
    split = scopes.per_step_ms(reduced)
    split["expsum_kernel_share"] = info["expsum_kernel_share"]
    print(json.dumps(split), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
