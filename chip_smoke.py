"""Smoke run of the Binary Bleed k-search over NMFk on a TPU, at paper scale.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the mesh paths on a four-chip host

Data is the paper's planted-rank matrix (Binary Bleed §IV-A): 1000 x 1100,
k_true = 8, searched over K = [2, 32] at select threshold 0.9 with
``ksearch``'s defaults (4 perturbations, 120 MU sweeps). The reference is
a grid over every k with the scalar ``nmfk_score`` under
``jax.default_matmul_precision("highest")``; it must find k = 8.

One chip: ``repro.launch.ksearch.main`` runs the search once each with
the ``threads``, ``batched`` and ``elastic`` executors, then the elastic
plane runs with the Pallas MU kernel (``use_kernel=True``), whose compiled
chunk step must hold a ``tpu_custom_call``. Every phase must return the
reference's ``k_optimal``.

``--four-chips``: the elastic executor on a (2 lanes x 2 data) mesh with
``--comm sync`` and ``--comm pipelined``, the ``sharded`` executor on 4
lanes, and the one-chip ``batched`` search they are compared with. The
mesh runs' V and slot pools must span all 4 devices.

Times printed here are smoke timings of one run, compilation included in
``seconds_total``, not benchmark numbers. Each phase prints one JSON line;
the last line is ``{"ok": true, "device": {...}}``. Any failed check exits
non-zero. Without a TPU, or without the repository's ``src/`` next to this
file, the script exits non-zero before printing a result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

N, M, K_TRUE, K_MIN, K_MAX = 1000, 1100, 8, 2, 32
THRESHOLD = 0.9
N_PERTURBS, NMF_ITERS = 4, 120


class _CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit skips the backend compile)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _phase(name: str, clock: _CompileClock, fn):
    """Run one phase; print its JSON line with wall and compile seconds."""
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
    out = fn()
    out = {
        "phase": name,
        **out,
        "seconds_total": round(time.perf_counter() - t0, 3),
        "compile_seconds": round(clock.seconds - c0, 3),
        "cache_hits": clock.cache_hits - h0,
    }
    print(json.dumps(out), flush=True)
    return out


def _ksearch(executor: str, *extra: str) -> dict:
    from repro.launch import ksearch

    out = ksearch.main([
        "--n", str(N), "--m", str(M), "--k-true", str(K_TRUE),
        "--k-min", str(K_MIN), "--k-max", str(K_MAX),
        "--threshold", str(THRESHOLD), "--executor", executor, "--quiet", *extra,
    ])
    keep = ("k_optimal", "visited", "n_visited", "compiled_shapes", "sweeps_run",
            "sweeps_saved", "waves", "ticks", "mesh", "comm", "seconds")
    return {k: out[k] for k in keep if k in out}


def _reference(v, key) -> dict:
    import jax

    from repro.core import grid_search
    from repro.factorization import make_nmfk_evaluator

    evaluate = make_nmfk_evaluator(v, key, n_perturbs=N_PERTURBS, nmf_iters=NMF_ITERS)
    scores = {}

    def scored(k):
        scores[int(k)] = s = evaluate(k)
        return s

    with jax.default_matmul_precision("highest"):
        res = grid_search(scored, (K_MIN, K_MAX), select_threshold=THRESHOLD)
    return {"k_optimal": res.k_optimal,
            "scores": {k: round(s, 4) for k, s in sorted(scores.items())}}


def _kernel_phase(v, key) -> dict:
    from repro.core import ElasticWavefrontScheduler, LaneRefillPolicy, make_space
    from repro.factorization.nmfk import elastic_chunk
    from repro.factorization.planes import NMFkElasticPlane

    plane = NMFkElasticPlane(
        v, key, n_perturbs=N_PERTURBS, nmf_iters=NMF_ITERS, k_pad=K_MAX, use_kernel=True
    )
    sched = ElasticWavefrontScheduler(
        make_space((K_MIN, K_MAX), THRESHOLD), refill=LaneRefillPolicy(order="pre")
    )
    t0 = time.perf_counter()
    result = sched.run(plane)
    dt = time.perf_counter() - t0
    # the chunk step this phase dispatched, lowered again from its own
    # shapes: Mosaic kernels appear as tpu_custom_call, an interpreted
    # kernel would not
    hlo = ""
    for batch, k_pad in sorted(plane.shapes_compiled):
        w, h, keff, pkeys = (x[:batch] for x in plane.pool)
        steps = keff  # any (batch,) int32 vector: only shapes reach the compiler
        hlo += elastic_chunk.lower(
            plane.v, w, h, keff, steps, pkeys, k_pad=k_pad, chunk=plane.chunk,
            epsilon=plane.epsilon, use_kernel=True,
        ).compile().as_text()
    if "tpu_custom_call" not in hlo:
        _fail("the kernel phase's compiled chunk step holds no tpu_custom_call")
    return {"k_optimal": result.k_optimal, "visited": sorted(result.visited_ks),
            "compiled_shapes": sorted(plane.shapes_compiled),
            "sweeps_run": plane.sweeps_run, "tpu_custom_call": True,
            "seconds": round(dt, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh paths and their one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.core import resolve_compile_cache
    from repro.factorization import nmf_data

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(json.dumps({"device": device}), flush=True)
    cache_dir = resolve_compile_cache()
    clock = _CompileClock()
    print(json.dumps({"compile_cache": cache_dir}), flush=True)

    key = jax.random.PRNGKey(0)
    v, _, _ = nmf_data(key, n=N, m=M, k_true=K_TRUE)
    t_start = time.perf_counter()

    if args.four_chips:
        if len(devs) != 4:
            _fail(f"--four-chips needs 4 devices, JAX sees {len(devs)}")
        k_ref = _phase("batched_one_chip", clock, lambda: _ksearch("batched"))["k_optimal"]
        if k_ref != K_TRUE:
            _fail(f"one-chip batched search found k={k_ref}, planted {K_TRUE}")
        runs = {
            "elastic_2x2_sync": ("elastic", "--lanes", "2", "--data-shards", "2", "--comm", "sync"),
            "elastic_2x2_pipelined": ("elastic", "--lanes", "2", "--data-shards", "2",
                                      "--comm", "pipelined"),
            "sharded_4_lanes": ("sharded", "--lanes", "4"),
        }
        for name, flags in runs.items():
            out = _phase(name, clock, lambda flags=flags: _ksearch(*flags))
            if out["k_optimal"] != k_ref:
                _fail(f"{name} found k={out['k_optimal']}, one-chip batched found {k_ref}")
            spans = [out["mesh"]["v_devices"], out["mesh"].get("pool_devices", 4)]
            if min(spans) != 4:
                _fail(f"{name} arrays span {spans} devices, not 4")
    else:
        k_ref = _phase("reference_grid_highest", clock, lambda: _reference(v, key))["k_optimal"]
        if k_ref != K_TRUE:
            _fail(f"the reference grid found k={k_ref}, planted {K_TRUE}")
        for executor in ("threads", "batched", "elastic"):
            out = _phase(executor, clock, lambda e=executor: _ksearch(e))
            if out["k_optimal"] != k_ref:
                _fail(f"{executor} found k={out['k_optimal']}, reference {k_ref}")
        out = _phase("elastic_pallas_kernel", clock, lambda: _kernel_phase(v, key))
        if out["k_optimal"] != k_ref:
            _fail(f"the kernel phase found k={out['k_optimal']}, reference {k_ref}")

    print(json.dumps({"seconds_total": round(time.perf_counter() - t_start, 3),
                      "compile_seconds_total": round(clock.seconds, 3),
                      "cache_hits_total": clock.cache_hits}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
