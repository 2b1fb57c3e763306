"""Elastic plane: host milliseconds in the program's ``retire`` spans, less
their ``score`` children, per chunk dispatch: the pool's concatenation, the
retire loop, finished lanes and compaction."""

from chipbench.hostloop import ms_per_chunk, self_us, spans


def read(window):
    if not spans(window, "retire"):
        return None
    return ms_per_chunk(window, self_us(window, "retire", "score"))
