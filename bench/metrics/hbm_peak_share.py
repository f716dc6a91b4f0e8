"""``hbm_peak_share`` (%): the most device memory in use during the
traced window, over the chip's memory, on the fullest chip.  Both come
from the device allocator's events in the profiler trace: in use is what
buffers and the runtime's reserved program scratch hold after an event,
the chip's memory is that plus what is free.  Only events inside the
window count, so the peak is that of the cell's own rounds, not of the
preload or of anything else in set-up.  Moves ``ops_per_s``: every
whole-slab copy a round makes is held here and is HBM traffic."""
from __future__ import annotations

from harness import devtrace


def read(ctx):
    got = devtrace.memory_peak(ctx.trace)
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
