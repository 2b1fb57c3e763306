"""Observability layer: tracer/metrics primitives, exports, and the
instrumented search paths (record/skip accounting == SearchResult)."""
import json
import math
import threading

import jax
import jax.numpy as jnp

from repro.core import (
    SimulatedScheduler,
    ThreadPoolScheduler,
    WavefrontScheduler,
    binary_bleed_recursive,
    binary_bleed_worklist,
    make_space,
)
from repro.obs import (
    NULL_TRACER,
    Metrics,
    NullTracer,
    Tracer,
    get_metrics,
    get_tracer,
    use_metrics,
    use_tracer,
)

SPACE = make_space((2, 30), 0.7, 0.2)


def square_wave(k, should_abort=None):
    return 1.0 if k <= 24 else (0.05 if k >= 28 else 0.5)


# -- tracer primitives ----------------------------------------------------------


def test_default_tracer_is_null_and_noop():
    assert isinstance(get_tracer(), NullTracer)
    assert not get_tracer().enabled
    # the disabled path hands out one shared span object — no buffering
    s1 = NULL_TRACER.span("fit", k=3)
    s2 = NULL_TRACER.span("score")
    assert s1 is s2
    with s1 as sp:
        sp.set(score=1.0)
    NULL_TRACER.event("skip", k=5)
    assert NULL_TRACER.events() == []


def test_span_records_duration_and_attrs():
    clock_t = [0.0]
    tr = Tracer(clock=lambda: clock_t[0])
    with tr.span("fit", track="resource-0", k=7) as sp:
        clock_t[0] = 0.5
        sp.set(score=0.9)
    (rec,) = tr.events()
    assert rec["name"] == "fit" and rec["ph"] == "X"
    assert rec["track"] == "resource-0"
    assert rec["dur"] == 0.5 * 1e6
    assert rec["args"] == {"k": 7, "score": 0.9}


def test_events_are_thread_safe():
    tr = Tracer()

    def emit(i):
        for j in range(100):
            tr.event("e", track=f"t{i}", j=j)

    threads = [threading.Thread(target=emit, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.events()) == 800


def test_use_tracer_restores_previous():
    tr = Tracer()
    before = get_tracer()
    with use_tracer(tr):
        assert get_tracer() is tr
    assert get_tracer() is before


def test_export_jsonl(tmp_path):
    tr = Tracer()
    tr.event("bound_merge", lo=-math.inf)  # non-finite must stay strict JSON
    with tr.span("fit", k=2):
        pass
    path = str(tmp_path / "t.jsonl")
    n = tr.export_jsonl(path)
    lines = [json.loads(line) for line in open(path)]
    assert n == len(lines) == 2
    assert lines[0]["args"]["lo"] == "-inf"


def test_export_perfetto_structure(tmp_path):
    tr = Tracer()
    with tr.span("fit", track="resource-0", k=2):
        pass
    tr.event("skip", track="resource-1", k=9, bound=math.inf)
    path = str(tmp_path / "t.json")
    tr.export_perfetto(path)
    doc = json.load(open(path))  # strict JSON: load must not need allow_nan
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"resource-0", "resource-1"}
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and all("dur" in e and "ts" in e and "tid" in e for e in spans)
    instants = [e for e in evs if e["ph"] == "i"]
    assert instants and instants[0]["args"]["bound"] == "inf"


def test_nested_spans_carry_parent_ids():
    tr = Tracer()
    with tr.span("tick"):
        with tr.span("chunk"):
            with tr.span("readback"):
                pass
        with tr.span("retire"):
            def other_thread():
                with tr.span("other-thread"):
                    pass

            worker = threading.Thread(target=other_thread)
            worker.start()
            worker.join(timeout=10)
            assert not worker.is_alive()
    with tr.span("publish"):
        pass
    recs = {r["name"]: r for r in tr.events()}
    assert len({r["id"] for r in recs.values()}) == len(recs) == 6
    assert recs["tick"]["parent"] is None and recs["publish"]["parent"] is None
    assert recs["chunk"]["parent"] == recs["tick"]["id"] == recs["retire"]["parent"]
    assert recs["readback"]["parent"] == recs["chunk"]["id"]
    # the parent is the span open on the same thread, not on another one
    assert recs["other-thread"]["parent"] is None
    # self time of tick = its duration minus its children's
    children = [r for r in recs.values() if r["parent"] == recs["tick"]["id"]]
    assert recs["tick"]["dur"] >= sum(r["dur"] for r in children)


def test_exports_carry_span_ids(tmp_path):
    tr = Tracer()
    with tr.span("tick"):
        with tr.span("refill", k=3):
            pass
    tr.event("skip", k=9)
    tr.add_span("lane", 0.0, 5.0, track="device:1")
    jsonl = str(tmp_path / "t.jsonl")
    tr.export_jsonl(jsonl)
    lines = {r["name"]: r for r in map(json.loads, open(jsonl))}
    assert lines["refill"]["parent"] == lines["tick"]["id"]
    assert lines["tick"]["parent"] is None and lines["lane"]["parent"] is None
    assert "id" not in lines["skip"]
    perfetto = str(tmp_path / "t.json")
    tr.export_perfetto(perfetto)
    spans = {e["name"]: e for e in json.load(open(perfetto))["traceEvents"] if e["ph"] == "X"}
    assert spans["refill"]["args"] == {"k": 3, "span_id": lines["refill"]["id"],
                                       "parent_id": lines["tick"]["id"]}
    assert spans["lane"]["args"]["parent_id"] is None


# -- metrics primitives ---------------------------------------------------------


def test_metrics_counters_gauges_histograms():
    m = Metrics()
    m.inc("ks_visited")
    m.inc("ks_visited", 4)
    m.set_gauge("heartbeat_age_max", 2.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("fit_seconds", v)
    assert m.counter("ks_visited") == 5
    assert m.gauge("heartbeat_age_max") == 2.5
    h = m.histogram("fit_seconds")
    assert h["count"] == 4 and h["sum"] == 10.0 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] in (2.0, 3.0)


def test_metrics_summary_is_json_safe():
    m = Metrics()
    m.set_gauge("lo_bound", -math.inf)
    m.observe("x", math.inf)
    s = m.summary()
    json.dumps(s, allow_nan=False)  # raises if any non-finite leaked
    assert s["gauges"]["lo_bound"] is None


def test_metrics_summary_visit_fraction():
    m = Metrics()
    m.set_gauge("ks_candidates", 20)
    m.inc("ks_visited", 5)
    m.inc("ks_skipped", 15)
    s = m.summary()["search"]
    assert s["visit_fraction"] == 0.25 and s["saved_vs_grid"] == 0.75
    assert s["ks_candidates"] == 20


def test_use_metrics_restores_previous():
    m = Metrics()
    before = get_metrics()
    with use_metrics(m):
        get_metrics().inc("x")
    assert get_metrics() is before
    assert m.counter("x") == 1


# -- instrumented search paths --------------------------------------------------


def _accounting(driver):
    tr, m = Tracer(), Metrics()
    with use_tracer(tr), use_metrics(m):
        res = driver(SPACE, square_wave)
    s = m.summary()["search"]
    assert s["ks_visited"] + s["ks_skipped"] == len(SPACE.ks)
    assert s["visit_fraction"] == res.visit_fraction
    names = {e["name"] for e in tr.events()}
    assert "record" in names
    return res, s, names


def test_worklist_accounting_matches_result():
    res, s, names = _accounting(binary_bleed_worklist)
    assert res.k_optimal == 24
    assert s["ks_skipped"] > 0 and "skip" in names


def test_recursive_accounting_matches_result():
    res, s, names = _accounting(binary_bleed_recursive)
    assert res.k_optimal == 24
    assert "subtree_prune" in names or "skip" in names


def test_wavefront_spans_and_accounting():
    tr, m = Tracer(), Metrics()
    with use_tracer(tr), use_metrics(m):
        sched = WavefrontScheduler(SPACE)
        res = sched.run(square_wave)
    s = m.summary()["search"]
    assert s["ks_visited"] == res.n_visited
    assert s["ks_visited"] + s["ks_skipped"] == len(SPACE.ks)
    waves = [e for e in tr.events() if e["name"] == "wave"]
    assert len(waves) == sched.n_dispatches
    assert all(e["track"] == "wavefront" for e in waves)
    assert m.histogram("wave_size")["count"] == sched.n_dispatches
    pubs = [e for e in tr.events() if e["name"] == "publish"]
    assert len(pubs) == sched.n_dispatches


def test_threadpool_spans_and_metrics():
    tr, m = Tracer(), Metrics()
    with use_tracer(tr), use_metrics(m):
        res = ThreadPoolScheduler(SPACE, 3).run(square_wave)
    assert m.counter("ks_visited") == res.n_visited
    assert m.counter("publish_count") == res.n_visited
    fits = [e for e in tr.events() if e["name"] == "fit"]
    assert len(fits) == res.n_visited
    assert all(e["track"].startswith("resource-") for e in fits)
    assert all("score" in e["args"] for e in fits)
    assert m.histogram("fit_seconds")["count"] == res.n_visited
    assert m.histogram("publish_latency_s")["count"] == res.n_visited
    workers = [e for e in tr.events() if e["name"] == "worker"]
    assert len(workers) == 3


def test_abort_event_fires_when_evaluator_polls():
    """An evaluator that polls ``should_abort`` after its k was pruned must
    produce exactly one abort event + ks_aborted increment for that k."""
    space = make_space((2, 10), 0.7)
    tr, m = Tracer(), Metrics()

    seen = []

    def evaluate(k, should_abort=None):
        seen.append(k)
        if should_abort is not None:
            should_abort()  # poll once mid-"fit"
        return 1.0 if k <= 6 else 0.0

    with use_tracer(tr), use_metrics(m):
        ThreadPoolScheduler(space, 1).run(evaluate)
    # serial worklist through one worker: ks pruned mid-flight never happen
    # here, so aborts are zero — the counter exists but stays 0
    assert m.counter("ks_aborted") == 0

    # now simulate a pruned-in-flight k: the wrapper fires once per poll run
    tr2, m2 = Tracer(), Metrics()
    with use_tracer(tr2), use_metrics(m2):
        sched = ThreadPoolScheduler(space, 1)
        coord = sched.coordinator
        from repro.core import Bounds

        def eval_abort(k, should_abort=None):
            coord.publish(Bounds(float(k), math.inf, k))  # prune self mid-fit
            assert should_abort() is True
            should_abort()  # second poll must not double-count
            return 0.5

        sched.run(eval_abort)
    aborts = [e for e in tr2.events() if e["name"] == "abort"]
    assert m2.counter("ks_aborted") == len(aborts) > 0


def test_schedule_trace_converter(tmp_path):
    space = make_space((2, 30), 0.7)
    trace = SimulatedScheduler(space, 4).run(lambda k: 1.0 if k <= 24 else 0.0)
    tr = trace.to_tracer()
    spans = [e for e in tr.events() if e["ph"] == "X"]
    assert len(spans) == len(trace.visits) + len(trace.aborted)
    tracks = {e["track"] for e in spans}
    assert tracks <= {f"resource-{r}" for r in range(4)}
    # logical seconds -> microseconds
    by_end = max(spans, key=lambda e: e["ts"] + e["dur"])
    assert by_end["ts"] + by_end["dur"] == trace.makespan * 1e6
    path = str(tmp_path / "sim.json")
    n = trace.export_perfetto(path)
    doc = json.load(open(path))
    assert len(doc["traceEvents"]) == n


def test_plane_compile_events_and_spans():
    from repro.factorization.planes import KMeansBatchPlane

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 3))
    tr, m = Tracer(), Metrics()
    with use_tracer(tr), use_metrics(m):
        plane = KMeansBatchPlane(x, key, k_pad=6, max_iters=5)
        plane.evaluate_batch([2, 3])
        plane.evaluate_batch([4, 5])  # same padded shape — no new compile
        plane.evaluate_batch([2, 3, 4])  # new padded batch shape
    assert m.counter("compile_count") == len(plane.shapes_compiled) == 2
    compiles = [e for e in tr.events() if e["name"] == "compile"]
    assert len(compiles) == 2
    fits = [e for e in tr.events() if e["name"] == "fit"]
    scores = [e for e in tr.events() if e["name"] == "score"]
    assert len(fits) == len(scores) == 3
    assert all(e["track"] == "device:0" for e in fits + scores)


def test_ksearch_trace_and_metrics_files(tmp_path):
    """Live (non-simulated) batched run: Perfetto-loadable trace with
    fit/score/publish spans + metrics whose visit_fraction matches the
    SearchResult accounting — the PR's acceptance path, scaled down."""
    from repro.launch.ksearch import main

    tpath = str(tmp_path / "t.perfetto.json")
    mpath = str(tmp_path / "m.json")
    out = main([
        "--n", "48", "--m", "56", "--k-max", "8", "--k-true", "4",
        "--n-perturbs", "2", "--nmf-iters", "30",
        "--executor", "batched", "--quiet",
        "--trace", tpath, "--metrics", mpath,
    ])
    doc = json.load(open(tpath))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"wave", "fit", "score", "publish", "record"} <= names
    mdoc = json.load(open(mpath))
    assert mdoc["summary"]["search"]["visit_fraction"] == mdoc["result"]["visit_fraction"]
    assert round(mdoc["result"]["visit_fraction"], 3) == out["visit_fraction"]
    assert mdoc["summary"]["search"]["ks_visited"] == out["n_visited"]
    assert mdoc["summary"]["search"]["compile_count"] >= 1
