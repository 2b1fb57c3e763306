"""Whole-window totals of the elastic host loop's program spans.

Each traced search keeps the records of its own ``repro.obs.Tracer``: a
span record has ``name``, ``dur`` (microseconds), and, where the program
links spans, ``id`` and ``parent`` (ids are unique within one search). A
program that has no such span gives no total, and its reader reads nothing.
"""
from __future__ import annotations


def _spans(search, name: str) -> list[dict]:
    return [r for r in search.records if r["ph"] == "X" and r["name"] == name]


def spans(window, name: str) -> list[dict]:
    """Every span called ``name`` in the window's traced searches."""
    return [r for s in window.searches if s.traced for r in _spans(s, name)]


def self_us(window, name: str, child: str) -> float:
    """Summed duration of the ``name`` spans less that of their ``child``
    spans, in microseconds."""
    total = 0.0
    for s in window.searches:
        if not s.traced:
            continue
        outer = _spans(s, name)
        ids = {r.get("id") for r in outer} - {None}
        total += sum(r["dur"] for r in outer)
        total -= sum(r["dur"] for r in _spans(s, child) if r.get("parent") in ids)
    return total


def ms_per_chunk(window, total_us: float) -> float | None:
    """``total_us`` over the window's ``chunk`` spans, in milliseconds."""
    chunks = len(spans(window, "chunk"))
    return total_us / 1e3 / chunks if chunks else None
