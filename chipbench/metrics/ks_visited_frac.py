"""Bleed layer: ks scored over |K|, the mean over the window's searches."""


def read(window):
    fracs = [s.n_visited / s.n_candidates for s in window.searches if s.n_candidates]
    return sum(fracs) / len(fracs) if fracs else None
