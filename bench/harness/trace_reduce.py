"""Reduction of a profiler trace to the numbers the per-layer readers use.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
normalised record: for each TPU (``/device:TPU:<n>`` plane) its operations
(the ``XLA Ops`` line, plus the ``Async XLA Ops`` line's collectives) and
its program executions (``XLA Modules``), and the host annotations the
benchmark and the program write (``bench.window``, ``bench.data`` and the
trainer's ``train`` step annotation), all as ``[name, start_ns, end_ns]``
on the trace's one clock.  ``reduce`` computes, over the measured window:

* busy time: the union of the operations' intervals, per chip;
* collective time: the union of the collectives' intervals, and the part
  of it during which no other operation runs on that chip (exposed);
* host gaps: the device's idle time between consecutive program
  executions;
* the breakdown: the operations that took the most device time, each with
  the program's source line where the trace names one, and the longest
  idle gaps, each named by the host annotation it fell in;
* per chip, the device time by the program's name scopes, outermost and
  chained, and the idle time by its host spans (``scope_ns``,
  ``scope_path_ns``, ``idle_by_span_ns``: see ``harness/scopes.py``, whose
  ``add`` ``load`` calls).

Loop and call operations (``while``, ``conditional``, ``call``) span the
operations of their bodies: they count towards busy time and nothing else.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Iterable, List, Tuple

from harness import scopes

HOST_NAMES = ("bench.window", "bench.data", "train")
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def op_name(hlo_text: str) -> str:
    """``%fusion.612 = (...) fusion(...)`` -> ``fusion.612``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(name: str) -> str:
    """``all-reduce-start.3`` -> ``all-reduce-start``; ``broadcast.58.clone2``
    -> ``broadcast``."""
    return re.sub(r"(\.\d+|\.clone\d*)+$", "", name)


def is_collective(name: str) -> bool:
    return op_kind(name).startswith(COLLECTIVES)


def load(path: str) -> dict:
    """The normalised record of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops",
                                     "XLA Modules"):
                    continue
                for e in line.events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    if line.name == "XLA Modules":
                        modules.append([e.name, s, s + d])
                        continue
                    name = op_name(e.name)
                    if line.name == "XLA Ops" or is_collective(name):
                        ops.append([name, s, s + d])
            chips[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_NAMES:
                        s = float(e.start_ns)
                        host.append([e.name, s, s + float(e.duration_ns)])
    sources = {}
    for js in glob.glob(os.path.join(os.path.dirname(path),
                                     "*.trace.json.gz")):
        sources.update(load_sources(js))
    return scopes.add({"chips": chips, "host": host, "sources": sources},
                      path)


NAME = re.compile(r'"name":"([^"]+)"')
SOURCE = re.compile(r'"source":"([^"]+)"')


def load_sources(path: str, limit: int = 32 << 20) -> dict:
    """``{op name: "repro/<module>.py:<line>"}`` from the first ``limit``
    characters of the profiler's ``.trace.json.gz`` beside the
    ``.xplane.pb``; the step's operations repeat every step, so the first
    steps name them all."""
    with gzip.open(path, "rt") as f:
        text = f.read(limit)
    out = {}
    for event in text.split('{"ph":"X"')[1:]:
        name, src = NAME.search(event), SOURCE.search(event)
        if name and src:
            out.setdefault(name.group(1), src.group(1).split("/src/", 1)[-1])
    return out


# ------------------------------------------------------------ intervals

def union(ivs: Iterable[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(ivs: Iterable[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def clip(ivs: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def minus(a: List[Interval], b: List[Interval]) -> float:
    """Length of union ``a`` not covered by union ``b``."""
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return length(a) - covered


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ------------------------------------------------------------ reduction

def window_of(record: dict) -> Interval:
    spans = [(s, e) for n, s, e in record["host"] if n == "bench.window"]
    if not spans:
        raise ValueError("the trace holds no bench.window annotation")
    return max(spans, key=lambda se: se[1] - se[0])


def host_label(gap: Interval, host: list) -> str:
    """What the host was doing during a device-idle gap: the share of the
    gap inside each annotation (``bench.data``, ``train``), the rest
    ``other host``; the largest first."""
    s0, e0 = gap
    share = {}
    for n, s, e in host:
        if n != "bench.window" and e > s0 and s < e0:
            share[n] = share.get(n, 0.0) + min(e, e0) - max(s, s0)
    share["other host"] = (e0 - s0) - sum(share.values())
    parts = sorted(share.items(), key=lambda kv: -kv[1])
    return " ".join(f"{n} {v / (e0 - s0):.0%}" for n, v in parts if v > 0)


def label(op: str, record: dict) -> str:
    src = record.get("sources", {}).get(op)
    return f"{op} {src}" if src else op


def reduce(record: dict, n_top: int = 10) -> dict:
    lo, hi = window_of(record)
    steps = sum(1 for n, s, e in record["host"]
                if n == "train" and lo <= s < hi)
    per_chip, op_time, idle = [], {}, []
    for plane in sorted(record["chips"]):
        chip = record["chips"][plane]
        ops = [(n, s, e) for n, s, e in chip["ops"] if e > lo and s < hi]
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        coll = union(clip([(s, e) for n, s, e in ops if is_collective(n)],
                          lo, hi))
        other = union(clip([(s, e) for n, s, e in ops
                            if not is_collective(n)
                            and op_kind(n) not in CONTAINERS], lo, hi))
        mods = sorted((s, e) for _, s, e in chip["modules"]
                      if e > lo and s < hi)
        mgaps = [b[0] - a[1] for a, b in zip(mods, mods[1:])]
        per_chip.append({"plane": plane, "busy_ns": length(busy),
                         "collective_ns": length(coll),
                         "exposed_ns": minus(coll, other),
                         "module_gaps_ns": mgaps,
                         "executions": len(mods)})
        for n, s, e in ops:
            if op_kind(n) not in CONTAINERS:
                op_time[n] = op_time.get(n, 0.0) + min(e, hi) - max(s, lo)
        for s, e in gaps(busy, lo, hi):
            idle.append((e - s, (s, e)))
    for c, split in zip(per_chip, scopes.reduce(record)["chips"]):
        c["scope_ns"] = split["scope_ns"]
        c["scope_path_ns"] = split["scope_path_ns"]
        c["idle_by_span_ns"] = split["idle_by_span_ns"]
    nchips = max(len(per_chip), 1)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:n_top]
    idle.sort(key=lambda x: -x[0])
    return {
        "window_ns": hi - lo,
        "steps": steps,
        "chips": per_chip,
        "busy_ns": sum(c["busy_ns"] for c in per_chip) / nchips,
        "device_ops": [[label(n, record), t / nchips * 1e-9]
                       for n, t in top_ops],
        "idle_gaps": [[host_label(gap, record["host"]), d * 1e-9]
                      for d, gap in idle[:n_top]],
    }
