"""Elastic plane: chunk dispatches per search, counted from the program's
``chunk`` spans."""


def read(window):
    counts = [len(s.spans("chunk")) for s in window.searches if s.traced]
    return sum(counts) / len(counts) if counts else None
