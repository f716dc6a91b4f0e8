"""Telemetry substrate: metric registry, per-round tracing, reporting,
cost model, skew diagnostics, and regression gating.

- :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket histograms
  with deterministic snapshots and cross-shard merge.
- :mod:`repro.obs.trace`  — bounded ring buffer of per-round events,
  JSONL + Chrome ``trace_event`` export; :func:`~repro.obs.trace.span`,
  the host spans the ``ShardedDHT`` wrappers open in the profiler's trace,
  and the ``gc`` span that marks full garbage collections there
  (installed on import).
- :mod:`repro.obs.report` — ``python -m repro.obs.report`` CLI rendering
  a round timeline, top-metrics summary, and ``--skew`` imbalance view.
- :mod:`repro.obs.costmodel` — calibrated α-β round-cost model fitted
  over trace events; throughput prediction at unreachable shard counts
  and the wire-vs-HLO traffic cross-check (DESIGN.md §11).
- :mod:`repro.obs.skew` — bin/bucket/L1-set imbalance summaries.
- :mod:`repro.obs.regress` — ``python -m repro.obs.regress`` noise-aware
  BENCH-trajectory regression gate for CI.

jit-safety rules in DESIGN.md §10.  ``OBS_DISABLED=1`` no-ops the lot.
"""
from . import costmodel, metrics, skew, trace
from .metrics import (counter_value, counting, disabled, enabled,
                      get_registry, inc, merge_snapshots, merge_wire_stats,
                      observe, set_enabled, set_gauge)
from .trace import (count_traced_rounds, get_tracer, install_gc_span,
                    record_event, record_round, span)

install_gc_span()


def __getattr__(name):
    # the CLI modules (python -m repro.obs.regress / .report) load
    # lazily so running them with -m doesn't double-import under runpy
    if name in ("regress", "report"):
        import importlib

        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "costmodel", "metrics", "regress", "skew", "trace",
    "counter_value", "counting", "disabled",
    "enabled", "get_registry", "inc", "merge_snapshots",
    "merge_wire_stats", "observe", "set_enabled", "set_gauge",
    "count_traced_rounds", "get_tracer", "record_event", "record_round",
    "span",
]
