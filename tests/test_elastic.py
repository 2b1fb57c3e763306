"""Elastic wavefront executor: convergence-gated chunked fits, lane refill,
cross-k warm starts, and the §III-D chunk-boundary abort path.

The fixed-iteration oracle for every comparison here is the batched plane
(``NMFkBatchPlane``): at ``tol=0`` / ``warm_start=False`` the elastic plane
runs the identical draw schedule in chunks, so curves must agree exactly.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ElasticWavefrontScheduler,
    LaneRefillPolicy,
    as_eval_plane,
    binary_bleed_search,
    make_space,
)
from repro.factorization.batching import WarmStartCache
from repro.factorization.planes import (
    KMeansBatchPlane,
    NMFkBatchPlane,
    NMFkElasticPlane,
)
from repro.factorization.synthetic import blob_data, nmf_data

KEY = jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=1)
def _fixture():
    v, _, _ = nmf_data(jax.random.fold_in(KEY, 2), n=48, m=52, k_true=4)
    return v


def _drain(plane):
    """Submit nothing new; tick until idle, collecting {k: score}."""
    scores = {}
    while not plane.idle:
        for k, s in plane.tick():
            scores[k] = s
    return scores


FIT = dict(n_perturbs=3, nmf_iters=45, k_pad=6, chunk=15, warm_start=False)
KS = [3, 4, 5]


@functools.lru_cache(maxsize=16)
def _elastic_curve(tol: float):
    """(scores over KS, total sweeps run) at the given convergence tol."""
    plane = NMFkElasticPlane(_fixture(), KEY, tol=tol, **FIT)
    for k in KS:
        plane.submit(k)
    scores = _drain(plane)
    return tuple(scores[k] for k in KS), plane.sweeps_run


# ---------------------------------------------------------------------------
# warm-start cache
# ---------------------------------------------------------------------------
def test_warm_cache_prefers_near_same_perturbation_then_smaller_k():
    c = WarmStartCache(window=8)
    w = {k: jnp.full((4, 8), float(k)) for k in (4, 5, 7, 8)}
    c.put(5, 0, w[5])
    c.put(7, 1, w[7])
    # distance tie (5 and 7 both at |k-6|=1): same perturbation wins
    k_src, w_src = c.nearest(6, 0)
    assert k_src == 5 and float(w_src[0, 0]) == 5.0
    # same distance + same perturbation on both sides: smaller k wins
    c2 = WarmStartCache(window=8)
    c2.put(4, 0, w[4])
    c2.put(8, 0, w[8])
    assert c2.nearest(6, 0)[0] == 4
    # closest k beats everything else
    assert c2.nearest(8, 1)[0] == 8


def test_warm_cache_window_and_fifo_eviction():
    c = WarmStartCache(window=2, max_ks=3)
    for k in (2, 3, 4):
        c.put(k, 0, jnp.zeros((2, 4)))
    assert c.nearest(9, 0) is None  # all further than window
    assert c.misses == 1
    c.put(5, 0, jnp.zeros((2, 4)))  # evicts k=2 (FIFO beyond max_ks)
    assert c.nearest(2, 0)[0] == 3
    assert c.hits == 1


# ---------------------------------------------------------------------------
# elastic plane vs the fixed-iteration batched oracle
# ---------------------------------------------------------------------------
def test_elastic_tol_zero_matches_batched_exactly():
    curve, sweeps = _elastic_curve(0.0)
    batched = NMFkBatchPlane(
        _fixture(), KEY, n_perturbs=FIT["n_perturbs"],
        nmf_iters=FIT["nmf_iters"], k_pad=FIT["k_pad"],
    )
    np.testing.assert_allclose(
        np.asarray(curve), np.asarray(batched.evaluate_batch(KS)), atol=1e-6,
        err_msg="tol=0 elastic fits must be draw-for-draw the batched fits",
    )
    assert sweeps == len(KS) * FIT["n_perturbs"] * FIT["nmf_iters"]


TOL_LADDER = [3e-2, 3e-3, 1e-3, 1e-4, 1e-6, 0.0]


@settings(max_examples=15, deadline=None)
@given(i=st.integers(min_value=0, max_value=len(TOL_LADDER) - 2))
def test_tightening_tol_converges_to_fixed_iteration_oracle(i):
    """Property: along a descending tol ladder, scores approach the tol=0
    oracle monotonically while sweeps run monotonically grow — the gate can
    only fire earlier at a looser tol."""
    oracle = np.asarray(_elastic_curve(0.0)[0])
    loose, tight = TOL_LADDER[i], TOL_LADDER[i + 1]
    c_loose, sw_loose = _elastic_curve(loose)
    c_tight, sw_tight = _elastic_curve(tight)
    dev_loose = float(np.max(np.abs(np.asarray(c_loose) - oracle)))
    dev_tight = float(np.max(np.abs(np.asarray(c_tight) - oracle)))
    assert sw_tight >= sw_loose
    assert dev_tight <= dev_loose + 1e-7


def test_elastic_search_matches_batched_search_and_accounting():
    v = _fixture()
    mk = dict(n_perturbs=3, nmf_iters=45, k_pad=6)
    plane = NMFkElasticPlane(v, KEY, tol=0.0, chunk=15, warm_start=False, **mk)
    res = ElasticWavefrontScheduler(make_space((2, 6), 0.8)).run(plane)
    batched = NMFkBatchPlane(v, KEY, **mk)
    ref = {k: s for k, s in zip(res.visited_ks, batched.evaluate_batch(res.visited_ks))}
    got = {rec.k: rec.score for rec in res.visits}
    assert res.k_optimal == 4
    for k in got:
        assert abs(got[k] - ref[k]) < 1e-6, f"k={k}: {got[k]} vs {ref[k]}"
    # the bench invariant holds over the whole search, evictions included
    assert plane.sweeps_run + plane.sweeps_saved == plane.sweeps_fixed_total
    assert len(res.visits) + (res.n_candidates - res.n_visited) == res.n_candidates


def test_elastic_api_executor_and_warm_start_agree_on_k_opt():
    v = _fixture()
    plane = NMFkElasticPlane(
        v, KEY, n_perturbs=3, nmf_iters=45, k_pad=6, tol=1e-4, chunk=15,
        warm_start=True,
    )
    res = binary_bleed_search(plane, (2, 6), 0.8, executor="elastic")
    assert res.k_optimal == 4
    assert plane.warm_cache.hits > 0  # refilled lanes actually warm-started
    assert plane.sweeps_run + plane.sweeps_saved == plane.sweeps_fixed_total


def test_elastic_cancel_evicts_inflight_and_credits_saved():
    v = _fixture()
    plane = NMFkElasticPlane(
        v, KEY, n_perturbs=3, nmf_iters=45, k_pad=6, tol=0.0, chunk=15,
        warm_start=False,
    )
    plane.submit(4)
    plane.submit(5)
    plane.tick()  # one chunk in flight for both ks
    assert plane.inflight_ks() == {4, 5}
    assert plane.cancel(5)
    assert plane.inflight_ks() == {4}
    assert plane.sweeps_saved > 0  # 5's unspent sweeps were credited
    assert not plane.cancel(5)  # idempotent: already gone
    scores = _drain(plane)
    assert set(scores) == {4}
    assert plane.sweeps_run + plane.sweeps_saved == plane.sweeps_fixed_total


# ---------------------------------------------------------------------------
# slot pool compaction: one donated call against the eager per-lane copies
# ---------------------------------------------------------------------------
class _EagerCompactPlane(NMFkElasticPlane):
    """The reference compaction: the chunk's output concatenated onto the
    pool, then, per freed slot in descending order, the slot's W row sliced
    out and the last occupied lane copied into it array by array."""

    moves = 0

    def _compact_pool(self, freed, w_new=None, h_new=None):
        if w_new is not None:
            batch = w_new.shape[0]
            self._w = jnp.concatenate([w_new, self._w[batch:]], axis=0)
            self._h = jnp.concatenate([h_new, self._h[batch:]], axis=0)
        rows = {}
        for i in freed:
            rows[i] = self._w[i]
            j = self._n_occ - 1
            if i != j:
                self._w = self._w.at[i].set(self._w[j])
                self._h = self._h.at[i].set(self._h[j])
                self._keff = self._keff.at[i].set(self._keff[j])
                self._pkeys = self._pkeys.at[i].set(self._pkeys[j])
                self._slot[i] = self._slot[j]
                self.moves += 1
            self._slot[j] = None
            self._n_occ = j
        return rows


# lanes of one k converge chunks apart, so retirements free slots mid-pool
COMPACT = dict(n_perturbs=3, nmf_iters=45, k_pad=6, tol=1e-3, chunk=4, slots=6,
               warm_start=True)
COMPACT_KS = [2, 3, 4, 5, 6]


def _assert_same_pool(plane, ref):
    for got, want in zip(plane.pool, ref.pool):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    lanes = [None if x is None else dataclasses.astuple(x) for x in plane._slot]
    assert lanes == [None if x is None else dataclasses.astuple(x) for x in ref._slot]


def _lockstep(planes, check):
    """Submit COMPACT_KS to every plane and tick them together until idle;
    after the third tick, cancel the k in flight in the first slot. Calls
    ``check()`` after every tick and after the cancel; returns the scores
    and the cancelled k."""
    for p in planes:
        for k in COMPACT_KS:
            p.submit(k)
    scores, ticks, cancelled = {}, 0, None
    while not planes[0].idle:
        outs = [p.tick() for p in planes]
        assert all(out == outs[0] for out in outs)
        scores.update(outs[0])
        check()
        ticks += 1
        if ticks == 3:
            cancelled = planes[0]._slot[0].k
            assert all(p.cancel(cancelled) for p in planes)
            check()
    assert all(p.idle for p in planes)
    return scores, cancelled


def test_pool_compaction_is_bit_identical_to_eager_copies():
    """After every tick, and after a cancel that evicts lanes mid-pool, the
    pool, the lane order and every score equal the eager reference's bit for
    bit: the compiled call only moves bytes."""
    plane = NMFkElasticPlane(_fixture(), KEY, **COMPACT)
    ref = _EagerCompactPlane(_fixture(), KEY, **COMPACT)
    moves = []

    def check():
        _assert_same_pool(plane, ref)
        moves.append(ref.moves)

    scores, cancelled = _lockstep([plane, ref], check)
    assert set(scores) == set(COMPACT_KS) - {cancelled}
    assert moves[3] > moves[2]  # the cancel left holes before the tail
    assert moves[-1] > moves[3] - moves[2]  # so did retirements


def test_pool_donation_spares_retained_rows_and_compiles_per_bucket():
    """Donating the pool never reaches a W row kept for scoring or warm
    starts, the live pool stays readable, the compiled call has one variant
    per chunk shape besides the cancel's gather, which the plane compiles
    when it is built, and ``pool_moves`` counts the reference's moves."""
    from repro.factorization.planes import _pool_compact_fn, _pool_shardings
    from repro.obs import Metrics, use_metrics

    ref = _EagerCompactPlane(_fixture(), KEY, **COMPACT)
    fn = _pool_compact_fn(_pool_shardings(ref.pool))
    fn.clear_cache()
    plane = NMFkElasticPlane(_fixture(), KEY, **COMPACT)
    assert fn._cache_size() == 1
    kept = []

    def check():
        rows = [w for t in plane._tasks.values() for w in t.w_parts.values()]
        rows += [w for by_p in plane.warm_cache._by_k.values() for w in by_p.values()]
        assert not any(w.is_deleted() for w in rows)
        kept.append(len(rows))

    metrics = Metrics()
    with use_metrics(metrics):
        _lockstep([plane, ref], check)
    assert max(kept) >= 3 * COMPACT["n_perturbs"]  # finished lanes' rows were checked
    assert all(np.isfinite(np.asarray(x)).all() for x in plane.pool)
    assert 1 < fn._cache_size() <= len(plane.shapes_compiled) + 1
    assert metrics.counter("pool_moves") == ref.moves > 0


def test_refill_policy_admits_up_to_backlog_cap():
    class FakePlane:
        slots = 4
        backlog = 0

    pol = LaneRefillPolicy(order="pre", max_backlog=2)
    p = FakePlane()
    assert pol.admit(p)
    p.backlog = 2
    assert not pol.admit(p)
    # default cap falls back to the plane's slot count
    assert LaneRefillPolicy().admit(p)
    # the candidate stream is exactly the pre-order traversal worklist
    assert sorted(pol.worklist([2, 3, 4, 5])) == [2, 3, 4, 5]
    assert pol.worklist([2, 3, 4, 5])[0] not in (2, 5)  # midpoint-first


# ---------------------------------------------------------------------------
# §III-D abort: chunk-boundary polling through the batch planes
# ---------------------------------------------------------------------------
def test_nmfk_chunked_scalar_matches_fused_when_never_aborted():
    v = _fixture()
    plane = NMFkBatchPlane(v, KEY, n_perturbs=3, nmf_iters=45, k_pad=6)
    got = plane.evaluate_one(4, should_abort=lambda: False)
    want = plane.evaluate_batch([4])[0]
    assert abs(got - want) < 1e-6
    assert plane.last_scalar_sweeps == 3 * 45


def test_nmfk_pruned_k_stops_consuming_sweeps():
    """Regression: the batched planes used to drop ``should_abort`` on the
    floor, so a §III-D prune still paid the full fit. Now the scalar path
    is chunked and the abort lands at the next chunk boundary."""
    v = _fixture()
    plane = NMFkBatchPlane(v, KEY, n_perturbs=3, nmf_iters=75, k_pad=6)
    polls = []

    def abort_after_first_chunk():
        polls.append(True)
        return len(polls) > 1

    score = plane.evaluate_one(4, should_abort=abort_after_first_chunk)
    # one chunk (abort_chunk sweeps x P lanes) ran, the remaining two never did
    assert plane.last_scalar_sweeps == plane.abort_chunk * 3
    assert plane.last_scalar_sweeps < 75 * 3
    # partial ensemble still scores (accounting only — the k was pruned)
    assert np.isfinite(score)


def test_nmfk_abort_before_first_chunk_is_void_score():
    v = _fixture()
    plane = NMFkBatchPlane(v, KEY, n_perturbs=2, nmf_iters=45, k_pad=6)
    score = plane.evaluate_one(4, should_abort=lambda: True)
    assert np.isnan(score)
    assert plane.last_scalar_sweeps == 0
    # NaN is void: neither threshold test selects it, bounds are untouched
    space = make_space((2, 6), 0.8, stop_threshold=0.1)
    assert not space.selects(score) and not space.stops(score)


def test_kmeans_chunked_scalar_abort():
    x, _ = blob_data(jax.random.fold_in(KEY, 3), n=120, d=4, k_true=4)
    plane = KMeansBatchPlane(x, KEY, score="silhouette", max_iters=25, k_pad=8)
    got = plane.evaluate_one(4, should_abort=lambda: False)
    want = plane.evaluate_batch([4])[0]
    assert abs(got - want) < 1e-5
    assert np.isnan(plane.evaluate_one(4, should_abort=lambda: True))


def test_batch_only_adapter_polls_abort_before_dispatch():
    calls = []

    class BatchOnly:
        def evaluate_batch(self, ks):
            calls.append(list(ks))
            return [1.0 for _ in ks]

    plane = as_eval_plane(BatchOnly())
    assert np.isnan(plane.evaluate_one(5, should_abort=lambda: True))
    assert calls == []  # pruned-while-queued k never paid for its fit
    assert plane.evaluate_one(5, should_abort=lambda: False) == 1.0
    assert calls == [[5]]


# ---------------------------------------------------------------------------
# the host loop under program spans, counters, and the profiler's trace
# ---------------------------------------------------------------------------
LOOP_SPANS = ("admit", "tick", "refill", "chunk", "readback", "retire", "score",
              "publish", "evict")


def _elastic_search_under(tracer):
    """A small elastic search with ``tracer`` and a fresh metrics registry."""
    from repro.obs import Metrics, use_metrics, use_tracer

    metrics = Metrics()
    with use_tracer(tracer), use_metrics(metrics):
        plane = NMFkElasticPlane(
            _fixture(), KEY, n_perturbs=3, nmf_iters=45, k_pad=6, tol=1e-4, chunk=15,
            warm_start=True,
        )
        res = ElasticWavefrontScheduler(make_space((2, 6), 0.8)).run(plane)
    return res, metrics


def test_tick_spans_nest_and_host_syncs_count_every_read():
    from repro.obs import Tracer

    tracer = Tracer()
    res, metrics = _elastic_search_under(tracer)
    spans = [r for r in tracer.events() if r["ph"] == "X"]
    by_id = {r["id"]: r for r in spans}
    children: dict = {}
    for r in spans:
        children.setdefault(r["parent"], []).append(r)

    def names(r):
        return sorted(c["name"] for c in children.get(r["id"], []))

    ticks = [r for r in spans if r["name"] == "tick"]
    assert ticks
    for tick in ticks:
        # a tick whose refill leaves no lane occupied holds only its refill
        assert names(tick) in (["chunk", "refill", "retire"], ["refill"])
    chunks = [r for r in spans if r["name"] == "chunk"]
    assert chunks and all(names(c) == ["readback"] for c in chunks)
    for r in spans:
        if r["name"] in ("admit", "tick", "publish", "evict"):
            assert r["parent"] is None
        elif r["name"] in ("refill", "chunk", "retire"):
            assert by_id[r["parent"]]["name"] == "tick"
        elif r["name"] == "score":
            assert by_id[r["parent"]]["name"] == "retire"
    scores = [r for r in spans if r["name"] == "score"]
    assert len(scores) == len(res.visits) > 0
    assert sorted(r["args"]["k"] for r in scores) == sorted(v.k for v in res.visits)
    # per-lane (k, sweeps) of every dispatch
    for c in chunks:
        a = c["args"]
        assert len(a["lane_ks"]) == len(a["lane_steps"]) == a["n_occ"]
        assert sorted(set(a["lane_ks"])) == a["ks"] and max(a["lane_steps"]) == a["sweeps"]
    assert sum(sum(c["args"]["lane_steps"]) for c in chunks) == metrics.counter("sweeps_run")
    refills = [r["args"] for r in spans if r["name"] == "refill"]
    assert all(a["slotted"] == a["warm"] + a["cold"] for a in refills)
    assert sum(a["warm"] for a in refills) == metrics.counter("warm_start_hits") > 0
    # one blocking read per occupied lane's error per chunk, one per scored k
    assert metrics.counter("host_syncs") == sum(c["args"]["n_occ"] for c in chunks) + len(scores)


def test_program_spans_are_host_events_in_the_profiler_trace(tmp_path):
    import collections
    import glob
    import os

    from jax.profiler import ProfileData

    from repro.obs import NULL_TRACER, Tracer

    _elastic_search_under(NULL_TRACER)  # compile outside the profiled search
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _elastic_search_under(tracer)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    host = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes if not plane.name.startswith("/device:")
        for line in plane.lines for ev in line.events if ev.name in LOOP_SPANS
    ]
    recorded = collections.Counter(r["name"] for r in tracer.events() if r["ph"] == "X")
    assert collections.Counter(name for name, _, _ in host) == recorded
    assert all(recorded[name] > 0 for name in ("tick", "refill", "chunk", "readback",
                                                "retire", "score"))
    # the same nesting, on the profiler's clock
    chunks = [(s, e) for name, s, e in host if name == "chunk"]
    for name, s, e in host:
        if name == "readback":
            assert any(cs <= s and e <= ce for cs, ce in chunks)


def test_null_tracer_records_nothing_and_opens_no_annotation(monkeypatch):
    from repro.obs import NULL_TRACER, Tracer
    from repro.obs import trace as trace_mod

    opened = []
    real = trace_mod._profiler_annotation

    def counting(name):
        opened.append(name)
        return real(name)

    def no_attrs(*args, **kwargs):
        raise AssertionError("chunk span attributes built with tracing off")

    monkeypatch.setattr(trace_mod, "_profiler_annotation", counting)
    with monkeypatch.context() as m:
        m.setattr(NMFkElasticPlane, "_chunk_attrs", no_attrs)
        res, metrics = _elastic_search_under(NULL_TRACER)
    assert res.k_optimal == 4
    assert opened == [] and NULL_TRACER.events() == []
    assert metrics.counter("host_syncs") > 0  # counters stay on without a tracer
    tracer = Tracer()
    _elastic_search_under(tracer)
    assert sorted(opened) == sorted(r["name"] for r in tracer.events() if r["ph"] == "X")
