"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load_events`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
and flattens it to ``(plane, line, name, start_ns, duration_ns)`` tuples;
``reduce_events`` works on those tuples alone, so it can be checked on a
hand-made list as well as on a recorded trace.

Device planes are those named ``/device:TPU:<i>``. On each, the ``XLA Ops``
line holds one event per executed operation and the ``XLA Modules`` line
one per executed program. The window is the host annotation
``chipbench_window`` (a ``jax.profiler.TraceAnnotation`` the harness puts
round the measured searches); each search inside it is annotated
``chipbench_search``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import math
import os
import re
from collections import defaultdict

WINDOW = "chipbench_window"
SEARCH = "chipbench_search"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# an op that only holds others (a loop, a branch, a call): its time is theirs
_CONTAINER = re.compile(r"\b(while|conditional|call)\(")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str) -> list[tuple[str, str, str, float, float]]:
    """Every event of a trace file (``.xplane.pb``, or gzip of one)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return [
        (plane.name, line.name, ev.name, float(ev.start_ns), float(ev.duration_ns))
        for plane in pd.planes
        for line in plane.lines
        for ev in line.events
    ]


def _module_base(name: str) -> str:
    """``jit_elastic_chunk(123...)`` -> ``jit_elastic_chunk``."""
    return name.split("(", 1)[0]


def _op_short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _enclosing(modules: list[tuple[float, float, str]], t: float) -> str:
    """Name of the program execution that holds time t on one device."""
    i = bisect.bisect_right(modules, (t, math.inf, "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return "?"


def _union_ns(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_events(events, top: int = 10) -> dict | None:
    """Busy and window seconds, per-program device seconds, top ops, idle gaps.

    Returns None when the trace holds no window annotation or no device
    operation inside it: there is then nothing to read.
    """
    windows = [(s, s + d) for p, l, n, s, d in events if not _DEVICE_PLANE.match(p) and n == WINDOW]
    if not windows:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    searches = sorted(
        (s, s + d) for p, l, n, s, d in events if not _DEVICE_PLANE.match(p) and n == SEARCH
    )
    ops_by_plane: dict[str, list[tuple[float, float]]] = defaultdict(list)
    modules_by_plane: dict[str, list[tuple[float, float, str]]] = defaultdict(list)
    op_events = []
    module_time: dict[str, float] = defaultdict(float)
    for p, l, n, s, d in events:
        if not _DEVICE_PLANE.match(p):
            continue
        lo, hi = max(s, w0), min(s + d, w1)
        if hi <= lo:
            continue
        if l == "XLA Ops":
            ops_by_plane[p].append((s, s + d))
            if not _CONTAINER.search(n):
                op_events.append((p, n, s, (hi - lo) * 1e-9))
        elif l == "XLA Modules":
            modules_by_plane[p].append((s, s + d, _module_base(n)))
            module_time[_module_base(n)] += (hi - lo) * 1e-9
    if not ops_by_plane:
        return None
    for mods in modules_by_plane.values():
        mods.sort()
    op_time: dict[str, float] = defaultdict(float)
    for p, n, s, sec in op_events:
        op_time[f"{_enclosing(modules_by_plane[p], s)}/{_op_short(n)}"] += sec
    busy = {p: _union_ns(_clip(iv, w0, w1)) for p, iv in ops_by_plane.items()}
    busy_s = sum(sum(e - s for s, e in iv) for iv in busy.values()) * 1e-9 / len(busy)

    def label(t: float) -> str:
        return "in_search" if any(s <= t < e for s, e in searches) else "between_searches"

    gaps = []
    for iv in busy.values():
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((label((a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_s,
        "devices": len(busy),
        "module_s": dict(module_time),
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": gaps[:top],
    }


def module_seconds(summary: dict, needle: str) -> float:
    """Device seconds of every program whose name contains ``needle``."""
    return sum(t for name, t in summary["module_s"].items() if needle in name)
