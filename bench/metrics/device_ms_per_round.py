"""``device_ms_per_round`` (ms): device busy time in the traced window
(union of op intervals, averaged over the chips) over the rounds the
window ran.  Moves ``ops_per_s``."""
from __future__ import annotations

from harness import devtrace


def read(ctx):
    busy = devtrace.busy_s(ctx.trace)
    if busy <= 0 or ctx.rounds <= 0:
        return None
    return busy * 1e3 / ctx.rounds
