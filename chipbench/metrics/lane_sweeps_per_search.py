"""Fits: MU lane-sweeps run per search, from the program's ``sweeps_run``
counter."""


def read(window):
    runs = [s.counters.get("sweeps_run", 0) for s in window.searches if s.traced]
    runs = [r for r in runs if r]
    return sum(runs) / len(runs) if runs else None
