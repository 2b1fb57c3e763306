"""Kernels: the least time of the window's MU chunk work (``leastwork``)
over the device time of the chunk program's executions in the trace, in %."""

from chipbench.leastwork import chunk_least_seconds
from chipbench.profile import module_seconds

PROGRAM = "elastic_chunk"


def read(window):
    if window.trace is None:
        return None
    device_s = module_seconds(window.trace, PROGRAM)
    spans = [sp for s in window.searches for sp in s.spans("chunk")]
    if device_s <= 0 or not spans:
        return None
    least = sum(chunk_least_seconds(sp, **window.shape, peak=window.peak) for sp in spans)
    return 100.0 * least / device_s
