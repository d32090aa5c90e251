"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--faults half_batch no_mix]

At the cell's own size, on the chip, with no measured window (training's
readings need none), for each seed:

* ``program``: the program's first steps against the reference -- the
  lower reading of each number is the largest over a dozen seeds or more;
* ``control`` (``--control-seeds``): the reference computed with int8
  matmul operands in the program's place, the nearest precision below the
  bfloat16 the configuration states -- the upper reading is the smallest;
* each fault (``--faults``), planted in the reference put in the program's
  place: ``half_batch`` (the loss over half of the tokens), ``no_mix``
  (no exchange between agents).  A state left unchanged reads 1 on
  ``change`` and needs no run.

One JSON line per reading on standard output, then a summary.  The
benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def as_program(ref: dict) -> dict:
    """A reference run's readings in the form the program's take."""
    return {"losses": [sum(l) / len(l) for l in ref["losses"]],
            "first_grad": ref["first_grad"], "change": ref["change"]}


def readings(ses, seed: int, control: bool, faults) -> list:
    """[(kind, {number: (value, where)})] for one seed."""
    from harness import check

    state, feed, prog, _ = ses.start(seed)
    del state, feed
    gc.collect()
    ref = ses.reference(seed)
    out = [("program", check.compare(prog, ref))]
    if control:
        out.append(("control", check.compare(
            as_program(ses.reference(seed, quant="int8")), ref)))
    for fault in faults:
        out.append((fault, check.compare(
            as_program(ses.reference(seed, fault=fault)), ref)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run
    run.use_cache()
    from harness import runner

    ses = runner.Session(ROOT, args.workload)
    table: dict = {}
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        with_ctl = seed in args.control_seeds
        for kind, gaps in readings(ses, seed,  with_ctl,
                                   args.faults if with_ctl else []):
            if kind == "program" and seed not in args.seeds:
                continue
            print(json.dumps({"seed": seed, "kind": kind, **{
                k: {"value": v, "at": w} for k, (v, w) in gaps.items()}}),
                flush=True)
            for k, (v, _) in gaps.items():
                table.setdefault(kind, {}).setdefault(k, []).append(v)
    summary = {kind: {k: {"max": max(v), "min": min(v), "n": len(v)}
                      for k, v in nums.items()}
               for kind, nums in table.items()}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
