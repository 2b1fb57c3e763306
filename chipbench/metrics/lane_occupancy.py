"""Elastic plane: occupied over dispatched lanes, the mean of the program's
``lane_occupancy`` histogram over every chunk of the window."""


def read(window):
    hists = [s.histograms.get("lane_occupancy") for s in window.searches if s.traced]
    hists = [h for h in hists if h and h["count"]]
    if not hists:
        return None
    return sum(h["sum"] for h in hists) / sum(h["count"] for h in hists)
