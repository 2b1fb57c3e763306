"""Device: 1 - (union of device operation intervals / traced window)."""


def read(window):
    if window.trace is None or window.trace["window_s"] <= 0:
        return None
    return 1.0 - window.trace["busy_s"] / window.trace["window_s"]
