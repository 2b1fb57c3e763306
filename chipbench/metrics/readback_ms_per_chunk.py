"""Elastic plane: host milliseconds in the program's ``readback`` spans (the
blocking read of the lane errors after a dispatch) per chunk dispatch."""

from chipbench.hostloop import ms_per_chunk, spans


def read(window):
    reads = spans(window, "readback")
    return ms_per_chunk(window, sum(r["dur"] for r in reads)) if reads else None
