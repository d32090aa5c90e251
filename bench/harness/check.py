"""The comparison that decides ``correct`` for a training cell.

Numbers, each against a limit of its own (``bench/limits/<cell>.json``):

* ``loss1``, ``loss2``, ... -- for each of the first steps, the relative
  gap between the loss the program reports (mean over agents) and the
  reference's.  Each step has its own limit: the first step's loss sees
  only the forward pass at the given weights, the later ones also every
  update and mix before them.
* ``first_grad`` -- the worst leaf's gap between the norm of the first
  gradient as the optimizer got it (read back from the program's exp-sum
  accumulators after one step: S_k = r_k g) and the reference's clipped
  gradient, relative to the larger of that leaf's reference norm and the
  median leaf's.
* ``change`` -- the same gap for the norm of each leaf's change over the
  first steps, as the next step would find the parameters.  Leaves whose
  reference gradient is under a thousandth of the median leaf's are left
  out: they move by round-off alone.

A leaf is one parameter of one agent, so every agent's shard is covered.
"""
from __future__ import annotations

import numpy as np

NOUGHT = 1e-3


def leaf_gaps(prog: list, ref: list, keep=None) -> dict:
    """``{"agent a leaf": gap}``; ``prog``/``ref`` are per-agent dicts of
    per-leaf norms."""
    leaves = [(a, k) for a in range(len(ref)) for k in ref[a]
              if keep is None or (a, k) in keep]
    med = float(np.median([ref[a][k] for a, k in leaves]))
    return {f"agent {a} {k}": abs(prog[a][k] - ref[a][k]) / max(ref[a][k],
                                                                 med)
            for a, k in leaves}


def worst(gaps: dict) -> tuple:
    where = max(gaps, key=gaps.get)
    return float(gaps[where]), where


def compare(prog: dict, ref: dict) -> dict:
    """``{number: (value, where)}``; ``prog`` has ``losses`` (one mean per
    step), ``first_grad`` and ``change``; ``ref`` is ``reference.run``'s
    result."""
    out = {}
    for t, (p, l) in enumerate(zip(prog["losses"], ref["losses"])):
        r = float(np.mean(l))
        out[f"loss{t + 1}"] = (abs(p - r) / abs(r), f"step {t + 1}")
    g = ref["first_grad"]
    med = float(np.median([v for d in g for v in d.values()]))
    keep = {(a, k) for a in range(len(g)) for k, v in g[a].items()
            if v >= NOUGHT * med}
    out["first_grad"] = worst(leaf_gaps(prog["first_grad"], g))
    out["change"] = worst(leaf_gaps(prog["change"], ref["change"], keep))
    return out


def judge(gaps: dict, limits: dict) -> tuple:
    """(correct, lines): every number that has a limit under it."""
    ok = True
    lines = {}
    for name, lim in limits["limits"].items():
        value, where = gaps[name]
        ok = ok and bool(np.isfinite(value)) and value <= lim
        lines[name] = {"value": float(value), "limit": lim, "at": where}
    return ok, lines
