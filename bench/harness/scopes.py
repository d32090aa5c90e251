"""The step's device time by the program's name scopes, and the device's
idle time by the program's host spans, from the same profiler trace that
``trace_reduce`` reads.

``add(record, path)`` adds to a ``trace_reduce.load`` record of the
``.xplane.pb`` at ``path``:

* ``scopes``: ``{op name: op_name path}`` from the ``tf_op`` argument that
  the profiler's ``.trace.json.gz`` carries beside ``source`` (on a TPU v5e
  e.g. ``jit(train_step)/vmap(transpose(jvp(train.fwd_bwd)))/dot_general``;
  the ``.xplane.pb`` events carry only their times);
* ``spans``: the program's host spans (``train.*``, ``gc.*``) as
  ``[name, start_ns, end_ns]``.

``reduce(record)`` computes over the measured window, per chip:

* ``scope_ns``: device time of the operations by the outermost name scope
  in their op_name path, wherever it sits among the transformations
  (``vmap(transpose(jvp(train.fwd_bwd)))``).  A fusion carries its root's
  op_name, so it counts wholly to its root's scope.  Operations whose path
  names no scope are ``unscoped``; operations the trace's first
  ``trace_reduce.load_sources`` bytes do not name are ``unseen``.  Loop and
  call operations are left out, as in ``trace_reduce``'s ``device_ops``.
* ``scope_path_ns``: the same time by the chain of every name scope in the
  path, outer to inner, joined by ``/`` (``scope_chain``:
  ``frodo.update/pallas.frodo_expsum_apply``), with the same ``unscoped``
  and ``unseen``; ``scope_ms`` reads it for any scope at any depth.
* ``idle_by_span_ns``: device-idle time by the innermost host span (the
  benchmark's annotations and the program's spans) covering each instant,
  ``other host`` where none does.

and the longest idle gaps, each labelled by the innermost spans in it, so
that the shares of a gap sum to 100%.  ``per_step_ms`` turns that into the
per-step numbers ``bench/split.py`` prints.
"""
from __future__ import annotations

import glob
import gzip
import os
import re

from harness import trace_reduce as tr

PROGRAM_SPANS = ("train.", "gc.")
OTHER = "other host"
UNSCOPED = "unscoped"
UNSEEN = "unseen"
# a name scope inside an op_name path: a dotted name between "/" or "("
# and "/", ")" or the end (argument names such as "state.params[...]" do
# not qualify)
SCOPE = re.compile(r"(?<=[/(])[A-Za-z_]\w*(?:\.\w+)+(?=[/)]|$)")
TF_OP = re.compile(r'"tf_op":"([^"]*)"')


def scope_of(path: str) -> str:
    m = SCOPE.search(path)
    return m.group(0) if m else UNSCOPED


def scope_chain(path: str) -> str:
    """Every name scope in the op_name path, outer to inner, joined by
    ``/``; a scope that a transformation or remat repeats further in
    (``train.fwd_bwd/vmap(transpose(jvp(train.fwd_bwd)))``) counts once.
    A fusion of operations from several places carries their paths joined
    by ``;``: its chain is that of the first path that names a scope, the
    one ``scope_of`` takes the outermost scope from."""
    for one in path.split(";"):
        chain = dict.fromkeys(SCOPE.findall(one))
        if chain:
            return "/".join(chain)
    return UNSCOPED


def load_scopes(path: str, limit: int = 32 << 20) -> dict:
    """``{op name: op_name path}`` ("" where the event has none) from the
    first ``limit`` characters of a ``.trace.json.gz``, as
    ``trace_reduce.load_sources`` reads ``source``."""
    with gzip.open(path, "rt") as f:
        text = f.read(limit)
    out = {}
    for event in text.split('{"ph":"X"')[1:]:
        name = tr.NAME.search(event)
        if name:
            op = TF_OP.search(event)
            out.setdefault(name.group(1),
                           op.group(1).rstrip(":") if op else "")
    return out


def add(record: dict, path: str) -> dict:
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPANS):
                        s = float(e.start_ns)
                        spans.append([e.name, s, s + float(e.duration_ns)])
    scopes = {}
    for js in glob.glob(os.path.join(os.path.dirname(path),
                                     "*.trace.json.gz")):
        scopes.update(load_scopes(js))
    record["scopes"], record["spans"] = scopes, spans
    return record


def innermost(spans: list, lo: float, hi: float) -> list:
    """``[[start, end, name]]`` tiling ``[lo, hi]``: at each instant the
    covering span that started last (of two that started together, the
    shorter), ``other host`` where none covers it."""
    cuts = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e)
                             if lo < t < hi)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    out, active, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k][1] <= a:
            active.append(by_start[k])
            k += 1
        active = [sp for sp in active if sp[2] > a]
        name = (max(active, key=lambda sp: (sp[1], -sp[2]))[0] if active
                else OTHER)
        if out and out[-1][2] == name:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def by_segment(ivs: list, segments: list) -> dict:
    """Length of the sorted disjoint intervals ``ivs`` by the name of the
    ``segments`` (sorted, tiling) they fall in."""
    out, j = {}, 0
    for s, e in ivs:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            out[name] = out.get(name, 0.0) + min(e, b) - max(s, a)
            k += 1
    return out


def label(shares: dict) -> str:
    total = sum(shares.values())
    parts = sorted(shares.items(), key=lambda kv: -kv[1])
    return " ".join(f"{n} {v / total:.0%}" for n, v in parts if v > 0)


def reduce(record: dict, n_top: int = 10) -> dict:
    lo, hi = tr.window_of(record)
    steps = sum(1 for n, s, e in record["host"]
                if n == "train" and lo <= s < hi)
    host = [sp for sp in record["host"] if sp[0] != "bench.window"]
    segments = innermost(host + record.get("spans", []), lo, hi)
    scopes = record.get("scopes", {})
    chips, idle = [], []
    for plane in sorted(record["chips"]):
        ops = [(n, s, e) for n, s, e in record["chips"][plane]["ops"]
               if e > lo and s < hi]
        scope_ns, scope_path_ns = {}, {}
        for n, s, e in ops:
            if tr.op_kind(n) in tr.CONTAINERS:
                continue
            key = scope_of(scopes[n]) if n in scopes else UNSEEN
            scope_ns[key] = scope_ns.get(key, 0.0) + min(e, hi) - max(s, lo)
            key = scope_chain(scopes[n]) if n in scopes else UNSEEN
            scope_path_ns[key] = (scope_path_ns.get(key, 0.0) + min(e, hi)
                                  - max(s, lo))
        gaps = tr.gaps(tr.union(tr.clip([(s, e) for _, s, e in ops],
                                        lo, hi)), lo, hi)
        chips.append({"plane": plane, "scope_ns": scope_ns,
                      "scope_path_ns": scope_path_ns,
                      "idle_by_span_ns": by_segment(gaps, segments)})
        idle += [(e - s, (s, e)) for s, e in gaps]
    idle.sort(key=lambda x: -x[0])
    return {"steps": steps, "chips": chips,
            "idle_gaps": [[label(by_segment([gap], segments)), d * 1e-9]
                          for d, gap in idle[:n_top]]}


def per_step_ms(reduced: dict) -> dict:
    """Per step, averaged over the chips: the step's scopes (``fwd_bwd_ms``
    ``train.fwd_bwd``, ``update_ms`` ``frodo.update``, ``mix_ms`` every
    ``consensus.*``, ``unscoped_ms``), the device-idle time while the host
    fetches the step's metrics (``fetch_idle_ms``, ``train.metrics``), and
    every scope, chain of scopes and span by name.  A number whose scope or
    span the trace does not hold is None; ``unscoped_ms`` is None unless the
    trace holds ``train.fwd_bwd``."""
    chips, steps = reduced["chips"], reduced["steps"]
    if not chips or not steps:
        return {}

    def ms(key: str, pick):
        got = [v for c in chips for k, v in c[key].items() if pick(k)]
        return sum(got) / len(chips) / steps * 1e-6 if got else None

    fwd_bwd = ms("scope_ns", lambda k: k == "train.fwd_bwd")
    return {
        "fwd_bwd_ms": fwd_bwd,
        "update_ms": ms("scope_ns", lambda k: k == "frodo.update"),
        "mix_ms": ms("scope_ns", lambda k: k.startswith("consensus.")),
        "unscoped_ms": (None if fwd_bwd is None else
                        ms("scope_ns", lambda k: k == UNSCOPED) or 0.0),
        "fetch_idle_ms": ms("idle_by_span_ns", lambda k: k == "train.metrics"),
        "steps": steps,
        **{f"{key[:-3]}_ms": {n: ms(key, lambda k, n=n: k == n)
                              for n in sorted({k for c in chips
                                               for k in c[key]})}
           for key in ("scope_ns", "scope_path_ns", "idle_by_span_ns")},
        "idle_gaps": reduced["idle_gaps"]}


def scope_ms(reduced: dict, name: str):
    """Device time per step, averaged over the chips, of the operations
    whose chain of scopes holds ``name`` at any depth (one scope, or a run
    of them joined by ``/``); None where none does.  ``reduced`` is
    ``reduce``'s or ``trace_reduce.reduce``'s result."""
    chips, steps = reduced["chips"], reduced["steps"]
    got = [v for c in chips for k, v in c["scope_path_ns"].items()
           if f"/{name}/" in f"/{k}/"]
    return sum(got) / len(chips) / steps * 1e-6 if got and steps else None
