"""``sort_ms_per_round`` (ms): device time of XLA's sort ops per round
(the routing's binning and rank sorts), from the profiler trace.  Moves
``ops_per_s``."""
from __future__ import annotations

from harness import devtrace


def is_sort(label: str) -> bool:
    return devtrace.opcode(label) == "sort"


def read(ctx):
    secs = devtrace.op_seconds(ctx.trace, is_sort)
    if not secs or ctx.rounds <= 0:
        return None
    return sum(secs.values()) * 1e3 / ctx.rounds
