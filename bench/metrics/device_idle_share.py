"""``device_idle_share`` (%): 1 minus the union of the device's op
intervals over the traced window, averaged over the chips (profiler
trace).  Moves ``ops_per_s``: idle device time is host time in the round."""
from __future__ import annotations

from harness import devtrace


def read(ctx):
    if not ctx.trace.device_ops or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(ctx.trace) / ctx.trace.window_s)
