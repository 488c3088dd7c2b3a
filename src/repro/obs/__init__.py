"""repro.obs — observability for the GraphAGILE stack.

Four parts:

* :mod:`repro.obs.tracer` — structured tracing (nestable spans,
  counters, instant events) exported as Chrome/Perfetto trace-event
  JSON, threaded through the compiler passes, every executor residency
  path, and the serving runtime.  Zero overhead when disabled.
  ``Tracer.anchor()``, called inside a ``jax.profiler`` session at the
  start and end of a window, puts the tracer's clock on the profile's,
  so its spans can be laid on the device's timeline.
* :mod:`repro.obs.attrib` — trace analysis: span-DAG reconstruction,
  critical path, per-span slack/stall, and the measured
  per-(layer, tile-block, kernel-mode) attribution table.
* :mod:`repro.obs.conformance` — measured-vs-predicted cost
  accounting: joins :mod:`repro.core.perfmodel` per-layer predictions
  with executor measurements, fits effective machine constants, and
  emits the ``ConformanceReport`` CI consumes.
* :mod:`repro.obs.trajectory` — per-metric tolerance-band comparison
  of fresh BENCH_*.json artifacts against committed baselines, the
  engine behind the ``benchmarks/check_trajectory.py`` CI gate.
"""
from .attrib import Span, TraceDAG, attribution_table, build_dag, \
    parse_spans
from .conformance import (ConformanceReport, build_report, fit_stage_bw,
                          ls_scale, nrmse)
from .tracer import (NullTracer, Tracer, disable_tracing,
                     enable_tracing, get_tracer, set_tracer, tracing)
from .trajectory import (DEFAULT_SPECS, FileReport, MetricResult,
                         MetricSpec, TrajectoryReport, compare_dirs,
                         compare_docs, compare_metrics, lookup)

__all__ = [
    "Tracer", "NullTracer", "get_tracer", "set_tracer",
    "enable_tracing", "disable_tracing", "tracing",
    "Span", "TraceDAG", "parse_spans", "build_dag",
    "attribution_table",
    "ConformanceReport", "build_report", "ls_scale", "nrmse",
    "fit_stage_bw",
    "MetricSpec", "MetricResult", "FileReport", "TrajectoryReport",
    "DEFAULT_SPECS", "compare_metrics", "compare_docs", "compare_dirs",
    "lookup",
]
