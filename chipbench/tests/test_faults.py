"""A run whose timed path is broken underneath comes out not correct.

Each test skips only the harness's look for a chip and drives the rest of a
run (set-up, window, check) on the CPU, with one fault planted in the
program: a chunk step that returns its state unchanged, a chunk that
advances only half of its lanes, a score altered where it is produced (in
every search, or in every third), a ``k_optimal`` moved up in every third
search, and one search that returns no ``k_optimal``. The cells run on one
chip, so they have no exchange between chips to leave out.
"""
import dataclasses
import itertools
import time

import pytest

from chipbench import harness

from .conftest import TINY

WINDOW_S = 3.0  # about ten searches of the tiny cell on the CPU


def _run(root):
    cell = harness.load_cell(root, TINY)
    return harness.run_cell(cell, 2**31 + 3, WINDOW_S, False, time.perf_counter())


def _in_every_third_search(monkeypatch, alter=lambda result: result):
    """Wraps the program's search so that ``alter`` changes the result of
    every third search (the warm-up's is the 0th); returns a probe that
    says whether the search now running is one of those."""
    import repro.core as core

    search = core.binary_bleed_search
    calls = itertools.count()
    faulty = [False]

    def every_third(*args, **kwargs):
        faulty[0] = next(calls) % 3 == 1
        result = search(*args, **kwargs)
        return alter(result) if faulty[0] else result

    monkeypatch.setattr(core, "binary_bleed_search", every_third)
    return lambda: faulty[0]


def test_sound_program_is_correct(bench_root):
    assert _run(bench_root)["correct"]


def test_unchanged_state(bench_root, monkeypatch):
    import repro.factorization.nmfk as nmfk

    chunk = nmfk.elastic_chunk

    def stuck(v, w, h, *args, **kwargs):
        return w, h, chunk(v, w, h, *args, **kwargs)[2]

    monkeypatch.setattr(nmfk, "elastic_chunk", stuck)
    assert not _run(bench_root)["correct"]


def test_half_the_lanes_left_out(bench_root, monkeypatch):
    import repro.factorization.nmfk as nmfk

    chunk = nmfk.elastic_chunk

    def half(v, w, h, *args, **kwargs):
        w2, h2, errs = chunk(v, w, h, *args, **kwargs)
        keep = w.shape[0] // 2
        return w2.at[keep:].set(w[keep:]), h2.at[keep:].set(h[keep:]), errs

    monkeypatch.setattr(nmfk, "elastic_chunk", half)
    assert not _run(bench_root)["correct"]


@pytest.mark.parametrize("delta", [-0.05, 0.05])
def test_score_altered_where_produced(bench_root, monkeypatch, delta):
    import repro.factorization.nmfk as nmfk

    score = nmfk.elastic_pooled_score

    def altered(*args, **kwargs):
        s = score(*args, **kwargs)
        return s._replace(min_silhouette=s.min_silhouette + delta)

    monkeypatch.setattr(nmfk, "elastic_pooled_score", altered)
    assert not _run(bench_root)["correct"]


@pytest.mark.parametrize("delta", [-0.05, 0.05])
def test_score_altered_in_every_third_search(bench_root, monkeypatch, delta):
    import repro.factorization.nmfk as nmfk

    score = nmfk.elastic_pooled_score
    faulty = _in_every_third_search(monkeypatch)

    def altered(*args, **kwargs):
        s = score(*args, **kwargs)
        return s._replace(min_silhouette=s.min_silhouette + delta) if faulty() else s

    monkeypatch.setattr(nmfk, "elastic_pooled_score", altered)
    out = _run(bench_root)
    assert not out["correct"]
    assert out["compared"]["searches_off"]["value"] >= 3


def test_k_optimal_moved_in_every_third_search(bench_root, monkeypatch):
    def moved(result):
        return dataclasses.replace(result, k_optimal=result.k_optimal + 1)

    _in_every_third_search(monkeypatch, moved)
    out = _run(bench_root)
    assert not out["correct"]
    assert out["compared"]["searches_off"]["value"] >= 3


def test_one_search_without_k_optimal(bench_root, monkeypatch):
    import repro.core as core

    search = core.binary_bleed_search
    calls = itertools.count()

    def loses_one(*args, **kwargs):
        result = search(*args, **kwargs)
        return dataclasses.replace(result, k_optimal=None) if next(calls) == 2 else result

    monkeypatch.setattr(core, "binary_bleed_search", loses_one)
    out = _run(bench_root)
    assert not out["correct"]
    assert out["compared"]["k_missing"]["value"] == 1
