"""Elastic plane: host milliseconds in the program's ``refill`` spans (slot
queued lanes, each one cold or warm init) per chunk dispatch."""

from chipbench.hostloop import ms_per_chunk, spans


def read(window):
    refills = spans(window, "refill")
    return ms_per_chunk(window, sum(r["dur"] for r in refills)) if refills else None
