"""Elastic plane: blocking device-to-host reads per search, the mean of the
program's ``host_syncs`` counter over the window's searches."""


def read(window):
    syncs = [s.counters["host_syncs"] for s in window.searches
             if s.traced and "host_syncs" in s.counters]
    return sum(syncs) / len(syncs) if syncs else None
