"""The readers of the elastic host loop's spans and counter, on hand-made
windows, and on a traced run of the tiny cell on the CPU."""
import time

import pytest

from chipbench import harness
from chipbench.harness import SearchRecord, Window

from .conftest import TINY

NEW = ("refill_ms_per_chunk", "readback_ms_per_chunk", "retire_ms_per_chunk",
       "score_ms_per_k", "host_syncs_per_search")


def _span(name, dur_us, sid=None, parent=None, **args):
    rec = {"name": name, "ph": "X", "ts": 0.0, "dur": float(dur_us), "track": "wavefront",
           "args": args}
    if sid is not None:
        rec.update(id=sid, parent=parent)
    return rec


def _search(records, counters=None):
    return SearchRecord(key=None, result=None, traced=True, records=records,
                        counters=counters or {})


def _window(*searches):
    return Window(list(searches), 1.0, {}, None, None)


# two searches, three chunks in all; ids repeat across searches, as they do
# in one Tracer per search
ONE = _search([
    _span("tick", 9000, 1), _span("refill", 1000, 2, 1), _span("chunk", 3000, 3, 1),
    _span("readback", 2000, 4, 3), _span("retire", 4000, 5, 1), _span("score", 1500, 6, 5),
    _span("tick", 5000, 7), _span("refill", 500, 8, 7), _span("chunk", 2000, 9, 7),
    _span("readback", 1500, 10, 9), _span("retire", 2500, 11, 7),
], {"host_syncs": 11})
TWO = _search([
    _span("tick", 8000, 1), _span("refill", 1500, 2, 1), _span("chunk", 2500, 3, 1),
    _span("readback", 2500, 4, 3), _span("retire", 4000, 5, 1), _span("score", 2500, 6, 5),
    _span("score", 3000, 7, 5),
    _span("tick", 100, 8), _span("refill", 50, 9, 8),  # refill left no lane occupied
], {"host_syncs": 20})
# the same loop as a program without these spans and counter reads it
PARENT = _search([_span("tick", 9000), _span("chunk", 3000, n_occ=8)], {"sweeps_run": 10})


def _read(name, window):
    import importlib

    return importlib.import_module(f"chipbench.metrics.{name}").read(window)


def test_refill_ms_per_chunk():
    # (1000 + 500 + 1500 + 50) us over 3 chunks
    assert _read("refill_ms_per_chunk", _window(ONE, TWO)) == pytest.approx(3.05 / 3)
    assert _read("refill_ms_per_chunk", _window(PARENT)) is None


def test_readback_ms_per_chunk():
    assert _read("readback_ms_per_chunk", _window(ONE, TWO)) == pytest.approx(6.0 / 3)
    assert _read("readback_ms_per_chunk", _window(PARENT)) is None


def test_retire_ms_per_chunk_leaves_out_its_scores():
    # retire (4000 + 2500 + 4000) less the scores under them (1500 + 2500 + 3000)
    assert _read("retire_ms_per_chunk", _window(ONE, TWO)) == pytest.approx(3.5 / 3)
    assert _read("retire_ms_per_chunk", _window(PARENT)) is None


def test_score_ms_per_k():
    assert _read("score_ms_per_k", _window(ONE, TWO)) == pytest.approx(7.0 / 3)
    assert _read("score_ms_per_k", _window(PARENT)) is None


def test_host_syncs_per_search():
    assert _read("host_syncs_per_search", _window(ONE, TWO)) == pytest.approx(15.5)
    assert _read("host_syncs_per_search", _window(PARENT)) is None


def test_tiny_cell_reads_every_host_loop_metric(bench_root, monkeypatch):
    cell = harness.load_cell(bench_root, TINY)
    window = {}
    run_window = harness.run_window

    def keep(*args, **kwargs):
        window["w"] = run_window(*args, **kwargs)
        return window["w"]

    monkeypatch.setattr(harness, "run_window", keep)
    out = harness.run_cell(cell, 2**31 + 11, 0.5, True, time.perf_counter())
    metrics = out["metrics"]
    assert all(metrics[name]["value"] > 0 for name in NEW)
    searches = window["w"].searches
    syncs = sum(sp["n_occ"] for s in searches for sp in s.spans("chunk"))
    syncs += sum(len(s.spans("score")) for s in searches)
    assert metrics["host_syncs_per_search"]["value"] == pytest.approx(syncs / len(searches))
    assert metrics["chunks_per_search"]["value"] > 0 and metrics["lane_occupancy"]["value"] > 0
