"""Scoring: host milliseconds per scored k, over the program's ``score``
spans (the pooled silhouette of a finished k and the read of its score)."""

from chipbench.hostloop import spans


def read(window):
    scores = spans(window, "score")
    return sum(r["dur"] for r in scores) / 1e3 / len(scores) if scores else None
