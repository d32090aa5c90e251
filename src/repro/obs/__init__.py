"""Observability: metrics sinks + jit-safe metric math + trace annotation.

See docs/observability.md.  Import surface is intentionally flat:

    from repro import obs
    obs.set_sink(obs.JsonlSink("experiments/run.jsonl"))
    obs.record("loss", 0.3, step=7)

    # inside jit: pure aux-pytree producers
    err = obs.consensus_error(stacked_params)
"""
from repro.obs.metrics import (JsonlSink, MemorySink, MetricsSink, NullSink,
                               consensus_error, frodo_step_metrics,
                               get_sink, global_norm, read_jsonl, record,
                               scalarize, set_sink, tree_sq_sum,
                               zeros_like_metrics)
from repro.obs.regress import (MetricDiff, Tolerance, compare_to_baseline,
                               format_report, is_timing_metric,
                               load_baseline, load_trajectories,
                               make_baseline, write_baseline)
from repro.obs.spans import (PhaseStat, Span, SpanRecorder, aggregate,
                             gc_spans, get_recorder, set_recorder, span,
                             span_paths, to_chrome_trace, to_records)
from repro.obs.timing import (ProfileWindow, StepTimer, step_annotation,
                              trace_scope)

__all__ = [
    "JsonlSink", "MemorySink", "MetricDiff", "MetricsSink", "NullSink",
    "PhaseStat", "ProfileWindow", "Span", "SpanRecorder", "StepTimer",
    "Tolerance", "aggregate", "compare_to_baseline",
    "consensus_error", "format_report", "frodo_step_metrics", "gc_spans",
    "get_recorder", "get_sink", "global_norm", "is_timing_metric",
    "load_baseline",
    "load_trajectories", "make_baseline", "read_jsonl", "record",
    "scalarize", "set_recorder", "set_sink", "span", "span_paths",
    "step_annotation", "to_chrome_trace", "to_records", "trace_scope",
    "tree_sq_sum", "write_baseline", "zeros_like_metrics",
]
