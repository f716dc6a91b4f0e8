"""Profiler trace -> device busy time, idle gaps and per-op device time.

The window of a ``--trace 1`` run is recorded with ``jax.profiler.trace``;
the harness marks its own host work with ``jax.profiler.TraceAnnotation``
spans (:data:`HOST_SPANS`).  :func:`load` reads the ``.xplane.pb`` that the
profiler wrote and keeps only what the reduction needs:

- per device plane (``/device:TPU:<n>``), the op events of its ``XLA Ops``
  line: label, start, end in ns, where the label is ``"<instruction>
  <opcode> <shape>"`` parsed from the HLO text the event carries
  (``sort.6 sort tuple``, ``route_pack_pallas.1 custom-call u32[65536,1,22]``);
- the harness's host spans, from any host line;
- the device allocator's ``MemoryAllocation`` / ``MemoryDeallocation``
  events, which the runtime records on a host line with the device's
  memory after the event: bytes held by buffers (``bytes_allocated``), by
  the runtime for its programs' scratch (``bytes_reserved``) and free
  (``bytes_available``).

Everything else is plain arithmetic over those lists, kept free of JAX so
that it can be tested on a small recorded trace (``bench/tests``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

HOST_SPANS = ("batch", "call", "block", "check")
OPS_LINE = "XLA Ops"
MEMORY_EVENTS = ("MemoryAllocation", "MemoryDeallocation")


@dataclasses.dataclass
class Trace:
    """Device op events per device and the harness's host spans, in ns on
    the profiler's one clock."""

    device_ops: dict[str, list[tuple[str, float, float]]]
    host_spans: list[tuple[str, float, float]]
    window: tuple[float, float]           # first and last host span bounds
    # per device index: (time, allocated, reserved, available) in bytes
    memory: dict[int, list[tuple[float, float, float, float]]] = \
        dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(
            device_ops={k: [tuple(e) for e in v]
                        for k, v in d["device_ops"].items()},
            host_spans=[tuple(e) for e in d["host_spans"]],
            window=tuple(d["window"]),
            memory={int(k): [tuple(e) for e in v]
                    for k, v in d.get("memory", {}).items()})


def op_label(hlo: str) -> str:
    """``"%fusion.1 = u32[8,26]{0,1:T(8,128)} fusion(...), kind=..."`` ->
    ``"fusion.1 fusion u32[8,26]"``; a name that is not HLO text is kept."""
    if " = " not in hlo:
        return hlo
    head, rhs = hlo.split(" = ", 1)
    if rhs.startswith("("):               # tuple shape: skip to its close
        depth = 0
        for j, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rhs[j + 1:]
    else:
        shape, _, rest = rhs.partition(" ")
        shape = shape.split("{")[0]
    opcode = rest.strip().split("(", 1)[0]
    return f"{head.lstrip('%')} {opcode} {shape}"


def opcode(label: str) -> str:
    parts = label.split(" ")
    return parts[1] if len(parts) > 2 else ""


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``; the window is spanned by the host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    spans = []
    memory: dict[int, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (op_label(e.name), float(e.start_ns),
                         float(e.end_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns)))
                    elif e.name in MEMORY_EVENTS:
                        st = dict(e.stats)
                        memory.setdefault(int(st.get("index_on_host", 0)),
                                          []).append((
                            float(e.start_ns),
                            float(st.get("bytes_allocated", 0)),
                            float(st.get("bytes_reserved", 0)),
                            float(st.get("bytes_available", 0))))
    spans.sort(key=lambda s: s[1])
    if not spans:
        raise ValueError(f"no harness host spans in {path}")
    window = (spans[0][1], max(s[2] for s in spans))
    for evs in memory.values():
        evs.sort()
    return Trace(device_ops=device_ops, host_spans=spans, window=window,
                 memory=memory)


def _clip(events, lo: float, hi: float):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, device: str) -> float:
    """Union of the device's op intervals inside the window."""
    lo, hi = trace.window
    return sum(e - s for s, e in union(
        (s, e) for _, s, e in _clip(trace.device_ops[device], lo, hi)))


def busy_s(trace: Trace) -> float:
    """Busy seconds averaged over the traced devices (0 if none)."""
    if not trace.device_ops:
        return 0.0
    return sum(busy_ns(trace, d) for d in trace.device_ops) * 1e-9 / len(
        trace.device_ops)


def memory_peak(trace: Trace) -> tuple[float, float] | None:
    """(peak bytes in use, bytes of memory) of the fullest device inside
    the window, from the allocator's events: in use is what buffers and the
    runtime's reserved scratch hold, the memory is that plus what is free.
    ``None`` where no event falls inside the window."""
    lo, hi = trace.window
    best = None
    for evs in trace.memory.values():
        inside = [(a + r, a + r + f) for t, a, r, f in evs if lo <= t <= hi]
        if not inside:
            continue
        peak, cap = max(inside)
        if best is None or peak / cap > best[0] / best[1]:
            best = (peak, cap)
    return best


def op_seconds(trace: Trace, match=None) -> dict[str, float]:
    """Device seconds per op name inside the window, averaged over the
    devices; ``match(name)`` keeps a subset."""
    lo, hi = trace.window
    tot: dict[str, float] = {}
    for evs in trace.device_ops.values():
        for name, s, e in _clip(evs, lo, hi):
            if match is None or match(name):
                tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    n = max(len(trace.device_ops), 1)
    return {k: v / n for k, v in tot.items()}


def idle_gaps(trace: Trace) -> list[tuple[str, float]]:
    """Idle intervals of the first device inside the window, each named by
    the host span that covers most of it (``none`` where no span does)."""
    if not trace.device_ops:
        return []
    lo, hi = trace.window
    dev = sorted(trace.device_ops)[0]
    busy = union((s, e) for _, s, e in _clip(trace.device_ops[dev], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    # the harness's spans follow one another on one thread: bisect to the
    # few that can overlap each gap
    spans = trace.host_spans
    ends = [e for _, _, e in spans]
    out = []
    for gs, ge in gaps:
        best, best_ns = "none", 0.0
        for name, s, e in spans[bisect.bisect_right(ends, gs):]:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > best_ns:
                best, best_ns = name, ov
        out.append((best, (ge - gs) * 1e-9))
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ``breakdown`` of the result line: the device ops that took most
    time, and the idle time summed by the host span it fell in, named
    ``"<span> (<gaps> gaps)"``."""
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    by_span: dict[str, list] = {}
    for name, sec in idle_gaps(trace):
        c = by_span.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += sec
    gaps = sorted(by_span.items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"{k} ({c} gaps)", s] for k, (c, s) in gaps]}
