"""Device: the least time of the window's MU chunk work over the traced
window's length, in %: the whole search's share of the chip's roofline."""

from chipbench.leastwork import chunk_least_seconds


def read(window):
    if window.trace is None or window.trace["window_s"] <= 0:
        return None
    spans = [sp for s in window.searches for sp in s.spans("chunk")]
    if not spans:
        return None
    least = sum(chunk_least_seconds(sp, **window.shape, peak=window.peak) for sp in spans)
    return 100.0 * least / window.trace["window_s"]
