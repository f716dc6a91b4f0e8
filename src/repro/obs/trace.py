"""Per-round trace recorder and the program's spans on the profiler's clock.

Every *executed* engine round (eager ``dht_execute``, each jitted call a
``ShardedDHT`` wrapper makes, each ``migration_step`` batch) lands one
:class:`RoundEvent` here via :func:`record_round`, carrying the op mix
and every scalar stat lane of the round (wire words both legs, fill
fraction, capacity vs. load, L1 hits, lock-retry rounds, epoch/watermark
stamps — whatever the round's ``estats`` held).  The ring is bounded
(``OBS_TRACE_MAXLEN``, default 4096 events) so long benchmark loops
cannot grow host memory without bound.

Exports: :meth:`TraceRecorder.to_jsonl` (one JSON object per line, the
schema in DESIGN.md §10) and :meth:`TraceRecorder.to_chrome_trace`
(Chrome ``trace_event`` JSON — load the file in https://ui.perfetto.dev
to see rounds on a timeline).

jit-safety: :func:`record_round` is host-only.  The engine calls it only
on the eager path (no tracers in sight); under ``jit``/``shard_map`` the
stat lanes ride the return value and the *caller's* host code (e.g. the
``ShardedDHT`` wrappers) records them.  The event's ``dur`` is measured
*after* the stat lanes are fetched, so it includes the device work those
scalars depend on.

Where the time goes is not measured here but in the profiler's own trace
(``jax.profiler.trace``), on the one clock that the device ops share
(DESIGN.md §10): :func:`span` opens a named host span there (the
``ShardedDHT`` wrappers' ``dht.*`` spans), :func:`install_gc_span` marks
Python's full garbage collections as ``gc`` spans, and the op engine
wraps its phases in ``jax.named_scope`` (:data:`PHASES`), so every device
op carries its phase in its ``op_name`` metadata.  With no profiler
attached a span costs one flag check in C++.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import time
from collections import deque
from typing import Sequence

from . import metrics

__all__ = ["RoundEvent", "TraceRecorder", "get_tracer", "record_round",
           "record_event", "start_fetch", "fetch_lanes",
           "count_traced_rounds", "PHASES", "span", "install_gc_span"]

# the op engine's device-side phases, each a ``jax.named_scope`` around
# its ops (``op_engine.dht_issue``)
PHASES = ("bin", "dispatch", "apply", "collect")


def span(name: str):
    """A host span named ``name`` in the profiler's trace, on the clock
    of the device ops: ``with span("dht.flush"): ...``.  No arguments are
    attached, so the trace shows the name as given."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


_gc_span = None              # the ``gc`` span of the running collection


def _gc_callback(phase: str, info: dict) -> None:
    global _gc_span
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_span = span("gc")
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def install_gc_span() -> None:
    """Mark each of Python's full (generation 2) garbage collections as a
    ``gc`` span in the profiler's trace (once per process)."""
    if _gc_callback not in gc.callbacks:
        gc.callbacks.append(_gc_callback)


# estats lanes -> registry counters (plain additive flush).
_COUNTER_LANES = {
    "wire_words": "engine.wire_words",
    "wire_send_words": "engine.wire_send_words",
    "wire_reply_words": "engine.wire_reply_words",
    "dropped": "engine.dropped",
    "mismatches": "engine.mismatches",
    "lock_tokens": "engine.lock_tokens",
    "rounds": "engine.write_rounds",
    "inserted": "engine.inserted",
    "evicted": "engine.evicted",
    "hits": "dht.hits",
    "misses": "dht.misses",
    "l1_hits": "l1.hits",
    # replication lanes (DESIGN.md §13): reads served by a successor
    # because the owner's liveness bit was down, and secondary copies
    # fanned into write rounds (write amplification = writes/acked)
    "fallback_reads": "replica.fallback_reads",
    "replica_writes": "replica.writes",
    "acked": "replica.acked_writes",
    # rows a bounded retry round re-issued after an overflow drop — the
    # final round's unrecovered drops stay on engine.dropped
    "requeued": "engine.requeued",
}


@dataclasses.dataclass
class RoundEvent:
    """One recorded round.  ``ts``/``dur`` in seconds on the host
    ``perf_counter`` clock; ``spans`` maps phase -> (start, dur)."""

    source: str
    ts: float
    dur: float
    spans: dict
    ops: dict
    stats: dict

    def to_json(self) -> dict:
        return {
            "source": self.source,
            "ts": self.ts,
            "dur": self.dur,
            "spans": {k: [v[0], v[1]] for k, v in self.spans.items()},
            "ops": dict(self.ops),
            "stats": dict(self.stats),
        }


class TraceRecorder:
    """Bounded ring buffer of :class:`RoundEvent`."""

    def __init__(self, maxlen: int | None = None):
        if maxlen is None:
            maxlen = int(os.environ.get("OBS_TRACE_MAXLEN", "4096"))
        self._events: deque[RoundEvent] = deque(maxlen=maxlen)
        self.n_recorded = 0        # lifetime count (ring may have evicted)

    @property
    def maxlen(self) -> int:
        return self._events.maxlen or 0

    def record(self, ev: RoundEvent) -> None:
        self._events.append(ev)
        self.n_recorded += 1

    def events(self) -> list[RoundEvent]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self.n_recorded = 0

    def to_jsonl(self, path: str) -> int:
        """One JSON object per line; returns the number of events."""
        evs = self.events()
        with open(path, "w") as f:
            for ev in evs:
                f.write(json.dumps(ev.to_json(), sort_keys=True) + "\n")
        return len(evs)

    def to_chrome_trace(self, path: str) -> int:
        """Chrome ``trace_event`` JSON (complete "X" events, µs clock):
        one event per round plus one per phase span, nested on the same
        track so perfetto renders rounds with their phase breakdown."""
        events = []
        for ev in self.events():
            ts_us = ev.ts * 1e6
            events.append({
                "name": ev.source, "cat": "round", "ph": "X",
                "ts": ts_us, "dur": max(ev.dur, 0.0) * 1e6,
                "pid": 1, "tid": 1,
                "args": {"ops": ev.ops, **ev.stats},
            })
            for phase, (start, dur) in ev.spans.items():
                events.append({
                    "name": phase, "cat": "phase", "ph": "X",
                    "ts": start * 1e6, "dur": max(dur, 0.0) * 1e6,
                    "pid": 1, "tid": 1, "args": {},
                })
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return len(events)


_TRACER = TraceRecorder()


def get_tracer() -> TraceRecorder:
    return _TRACER


def start_fetch(stats: dict) -> None:
    """Start the device-to-host copy of each scalar stat lane.  Called at
    dispatch, the copies queue behind the round's program, so the flush's
    :func:`fetch_lanes` finds the values on the host when the round ends."""
    import jax

    for v in stats.values():
        if isinstance(v, jax.Array) and v.ndim == 0:
            v.copy_to_host_async()


def fetch_lanes(stats: dict) -> dict:
    """The scalar stat lanes as Python numbers, in one device-to-host
    fetch.  Each lane's shape is read before any transfer: vector lanes
    (``code``, ``bin_counts``, watermark vectors) are never fetched, and
    Python numbers pass through unchanged.  The 0-d device lanes go to
    the host in one ``jax.device_get``, which starts every copy before it
    waits on any; ``engine.host_fetches`` counts these fetches."""
    import jax

    scal = {}
    for k, v in stats.items():
        if isinstance(v, (bool, int, float)):
            scal[k] = v
        elif getattr(v, "shape", None) == ():
            if getattr(getattr(v, "dtype", None), "kind", "O") in "biuf":
                scal[k] = v
    dev = {k: v for k, v in scal.items() if isinstance(v, jax.Array)}
    if dev:
        metrics.inc("engine.host_fetches")
        scal.update(jax.device_get(dev))
    return {k: v.item() if hasattr(v, "item") else v
            for k, v in scal.items()}


def record_round(source: str, stats: dict, *, ops: dict | None = None,
                 t_start: float | None = None,
                 phase_marks: Sequence[tuple[str, float]] = (),
                 dur: float | None = None) -> None:
    """Flush one executed round: trace event + registry accumulation.

    ``stats`` is the round's stat-lane dict (jax scalars fine — fetched
    here in one :func:`fetch_lanes`; host numbers pass through).
    ``phase_marks`` is ``[(phase, start_time), ...]`` in order; each
    phase ends where the next begins, the last at record time.
    ``engine.rounds`` advances by the round's ``dispatch_rounds`` lane
    (default 1) — this is the host-side executed-round counter that jit
    trace-caching cannot defeat.  ``dur`` overrides the measured
    duration — for callers that timed the round externally (e.g. a
    benchmark recording a median-of-k jitted call as one event)."""
    if not metrics.enabled():
        return
    scal = fetch_lanes(stats)
    now = time.perf_counter()
    ts = t_start if t_start is not None else now
    if dur is None:
        dur = max(now - ts, 0.0) if t_start is not None else 0.0
    else:
        dur = max(float(dur), 0.0)

    reg = metrics.get_registry()
    reg.inc("engine.rounds", int(scal.get("dispatch_rounds", 1)))
    for lane, name in _COUNTER_LANES.items():
        if lane in scal:
            reg.inc(name, int(scal[lane]))
    if "fill_frac" in scal:
        reg.observe("engine.fill_frac", scal["fill_frac"],
                    edges=metrics.FRACTION_EDGES)
    # per-round skew lanes (DESIGN.md §11): bin-count imbalance and the
    # hottest-shard traffic fraction ride every round's estats
    if "bin_imbalance" in scal:
        reg.observe("engine.bin_imbalance", scal["bin_imbalance"],
                    edges=metrics.RATIO_EDGES)
    if "hot_frac" in scal:
        reg.observe("engine.hot_frac", scal["hot_frac"],
                    edges=metrics.FRACTION_EDGES)
    # issue/commit pipelining lanes (DESIGN.md §12): what fraction of the
    # round's latency the caller hid by working between the two halves
    if "overlap_frac" in scal:
        reg.observe("engine.overlap_frac", scal["overlap_frac"],
                    edges=metrics.FRACTION_EDGES)
    if "hidden_us" in scal:
        reg.observe("engine.hidden_us", scal["hidden_us"],
                    edges=metrics.LATENCY_EDGES_US)
    if t_start is not None or dur > 0.0:
        reg.observe("engine.round_latency_us", dur * 1e6,
                    edges=metrics.LATENCY_EDGES_US)
    total_ops = 0
    for kind, n in (ops or {}).items():
        reg.inc(f"engine.ops.{kind}", int(n))
        total_ops += int(n)
    if total_ops:
        reg.observe("engine.batch_size", total_ops,
                    edges=metrics.SIZE_EDGES)
    if "l1_hits" in scal:
        reg.inc("l1.queries", total_ops)

    spans = {}
    marks = list(phase_marks)
    for i, (phase, start) in enumerate(marks):
        end = marks[i + 1][1] if i + 1 < len(marks) else now
        spans[phase] = (start, max(end - start, 0.0))
    _TRACER.record(RoundEvent(source=source, ts=ts, dur=dur,
                              spans=spans, ops=dict(ops or {}),
                              stats=scal))


def record_event(source: str, stats: dict | None = None, *,
                 t_start: float | None = None,
                 ops: dict | None = None) -> None:
    """Trace-only event (no ``engine.rounds`` side effect) — for host
    steps that wrap already-recorded rounds, e.g. one
    ``migration_step`` batch or a benchmark iteration."""
    if not metrics.enabled():
        return
    now = time.perf_counter()
    ts = t_start if t_start is not None else now
    _TRACER.record(RoundEvent(
        source=source, ts=ts, dur=max(now - ts, 0.0), spans={},
        ops=dict(ops or {}), stats=fetch_lanes(stats or {})))


def count_traced_rounds(fn, *args) -> int:
    """Collective data rounds in ONE traced execution of ``fn(*args)``.

    Traces a fresh lambda through ``jax.make_jaxpr`` — the wrapper is a
    new callable every call, so jit's trace cache cannot elide the trace
    — and counts ``routing.dispatch`` invocations during it.  This is
    the supported replacement for the PR 3 ``round_count`` global, which
    a warm trace cache silently froze at zero."""
    import jax

    prev = metrics.set_enabled(True)
    try:
        with metrics.counting() as c:
            jax.make_jaxpr(lambda *a: fn(*a))(*args)
    finally:
        metrics.set_enabled(prev)
    return c.delta
