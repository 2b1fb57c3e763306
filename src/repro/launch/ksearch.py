"""Distributed Binary Bleed k-search driver — the paper end-to-end.

Composes the whole system: the mesh is carved into R sub-meshes
("resources" in the paper's terms); Binary Bleed chunks K over them
(Algorithm 2 + pre-order sort) and each resource evaluates its k values —
each evaluation itself a *distributed* NMFk fit over that resource's
devices (pyDNMFk mode). Pruning broadcasts flow through the coordinator
(in-process for threads, file-based across hosts), and the journal makes
the search restartable mid-flight.

With fewer devices than resources (one chip, or the CPU) every resource
shares the visible devices and resources are threads — the control plane
is the one a pod runs; swap ``make_submeshes`` for pod slices there.

The persistent compile cache is always on: it lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache/``
at the checkout root (``repro.core.resolve_compile_cache``).

  PYTHONPATH=src python -m repro.launch.ksearch --k-max 16 --k-true 5 \
      --resources 4 --early-stop

``--executor sharded`` replaces threads with the mesh-sharded wavefront
plane: one jit'd dispatch fits a whole frontier, k-lanes split over the
mesh's ``lane`` axis and (``--data-shards > 1``) V's rows over ``data``.
Validate on CPU with 8 virtual devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.ksearch --executor sharded --k-max 32

``--executor elastic`` replaces fixed-iteration waves with continuous
batching over fit-chunks: lanes retire as soon as their fit converges
(``--tol``), freed slots refill from the worklist mid-stream, refilled ks
warm-start from completed neighbors (``--warm-start``), and §III-D prunes
evict in-flight ks between chunks. Shard-maps like ``sharded`` when
``--lanes`` / ``--data-shards`` are given:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.ksearch --executor elastic --k-max 32
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.core import (
    ElasticWavefrontScheduler,
    FileCoordinator,
    InProcessCoordinator,
    LaneRefillPolicy,
    SearchSpace,
    ThreadPoolScheduler,
    WavefrontScheduler,
    make_space,
    resolve_compile_cache,
)
from repro.factorization.distributed import distributed_nmf, make_local_mesh
from repro.factorization.nmfk import nmfk_score
from repro.factorization.planes import NMFkBatchPlane, NMFkElasticPlane
from repro.factorization.synthetic import nmf_data
from repro.launch.mesh import SubmeshPool, make_wave_mesh
from repro.obs import NULL_TRACER, Metrics, Tracer, use_metrics, use_tracer


def make_submeshes(num_resources: int):
    """Carve jax.devices() into `num_resources` sub-meshes (round-robin).

    On a pod this is `mesh.devices.reshape(R, -1)` slices; on CPU every
    resource gets the single device (threads share it)."""
    devs = jax.devices()
    if len(devs) >= num_resources:
        per = len(devs) // num_resources
        return [make_local_mesh(per) for _ in range(num_resources)]
    return [make_local_mesh(len(devs)) for _ in range(num_resources)]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=96)
    ap.add_argument("--m", type=int, default=104)
    ap.add_argument("--k-true", type=int, default=5)
    ap.add_argument("--k-min", type=int, default=2)
    ap.add_argument("--k-max", type=int, default=16)
    ap.add_argument("--resources", type=int, default=4)
    ap.add_argument("--threshold", type=float, default=0.9)
    ap.add_argument("--early-stop", action="store_true")
    ap.add_argument("--stop-threshold", type=float, default=0.1)
    ap.add_argument("--order", default="pre", choices=["pre", "in", "post"])
    ap.add_argument("--n-perturbs", type=int, default=4)
    ap.add_argument("--nmf-iters", type=int, default=120)
    ap.add_argument("--journal", default=None, help="dir for FileCoordinator (restartable)")
    ap.add_argument("--distributed-fit", action="store_true",
                    help="run each NMF fit via shard_map over the resource's sub-mesh")
    ap.add_argument("--executor", default="threads",
                    choices=["threads", "batched", "sharded", "elastic"],
                    help="threads: one fit per k per worker; batched: wavefront "
                    "frontiers as one padded vmapped NMFk fit per wave; sharded: "
                    "wavefront frontiers shard_map'd over a (lane, data) mesh — "
                    "parallel-over-k across lanes, distributed-within-k when "
                    "--data-shards > 1; elastic: continuous batching over "
                    "fit-chunks — lanes retire on per-fit convergence (--tol), "
                    "freed slots refill from the worklist, new ks warm-start "
                    "from neighbors (shard-maps like sharded when --lanes or "
                    "--data-shards is given)")
    ap.add_argument("--max-wave", type=int, default=None,
                    help="cap ks per batched dispatch (batched/sharded executors)")
    ap.add_argument("--lanes", type=int, default=None,
                    help="lane-axis size of the sharded mesh (default: all "
                    "visible devices / --data-shards)")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="data-axis size of the sharded mesh: each lane's NMF "
                    "fit row-shards V over this many devices (pyDNMFk mode)")
    ap.add_argument("--comm", default="sync", choices=["sync", "pipelined"],
                    help="collective schedule of the data-sharded fits: sync "
                    "blocks each MU sweep on the Gram all-reduces; pipelined "
                    "decomposes them into psum_scatter + ring all-gather and "
                    "overlaps the in-flight reduction with the local W-update "
                    "(one-sweep-stale H, final sync sweep). Only meaningful "
                    "with --executor sharded and --data-shards > 1")
    ap.add_argument("--tol", type=float, default=1e-3,
                    help="elastic convergence gate: a lane retires when its "
                    "rel_error improved by less than this over the last chunk "
                    "(chunk-size dependent; <= 0 disables the gate — every "
                    "lane then runs exactly --nmf-iters sweeps, reproducing "
                    "the batched executor draw-for-draw)")
    ap.add_argument("--fit-chunk", type=int, default=25,
                    help="elastic chunk size: MU sweeps per dispatch between "
                    "convergence checks / refills / abort polls")
    ap.add_argument("--warm-start", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="seed refilled elastic lanes from the nearest "
                    "completed k's W (column pad/truncate + re-normalize); "
                    "--no-warm-start cold-starts every lane")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="write a search trace: Chrome-trace/Perfetto JSON "
                    "(open at ui.perfetto.dev), or JSONL if OUT ends in .jsonl")
    ap.add_argument("--metrics", default=None, metavar="OUT",
                    help="write the metrics summary JSON (counters/gauges/"
                    "histograms + pruning-efficiency block)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    # before the first jit dispatch: earlier compiles are not retro-cached
    resolve_compile_cache()

    key = jax.random.PRNGKey(0)
    v, _, _ = nmf_data(key, n=args.n, m=args.m, k_true=args.k_true)
    pool = SubmeshPool(make_submeshes(args.resources))

    def evaluate(k: int, should_abort=None) -> float:
        sub = jax.random.fold_in(key, k)
        if args.distributed_fit:
            # paper's distributed mode: the fit itself is sharded over this
            # *worker's* leased sub-mesh (a worker-identity resource — keying
            # by k collides concurrent workers onto one device group);
            # scoring still ensembles perturbations (cheap at this scale).
            res = distributed_nmf(v, int(k), sub, pool.acquire(), iters=args.nmf_iters)
            del res
        sc = nmfk_score(v, int(k), sub, n_perturbs=args.n_perturbs, nmf_iters=args.nmf_iters)
        return float(sc.min_silhouette)

    space = make_space(
        (args.k_min, args.k_max),
        args.threshold,
        args.stop_threshold if args.early_stop else None,
    )

    # telemetry: a real tracer only when requested (NullTracer otherwise —
    # allocation-free hot path); metrics are always on but scoped to this
    # run so summary()'s visit_fraction reflects exactly this search.
    tracer = Tracer() if args.trace else NULL_TRACER
    metrics = Metrics()
    with use_tracer(tracer), use_metrics(metrics):
        result, dt, extra = _run_search(args, ap, space, v, key, evaluate)

    out = _emit(args, result, dt, extra, tracer, metrics)
    return out


def _run_search(args, ap, space, v, key, evaluate):
    if args.executor == "elastic":
        if not args.quiet:
            for flag, used in (("--journal", args.journal),
                               ("--distributed-fit", args.distributed_fit),
                               ("--resources", args.resources != ap.get_default("resources")),
                               ("--max-wave", args.max_wave is not None)):
                if used:
                    print(f"note: {flag} is ignored by the elastic executor")
        mesh = None
        if args.lanes is not None or args.data_shards > 1:
            mesh = make_wave_mesh(lanes=args.lanes, data=args.data_shards)
        plane = NMFkElasticPlane(
            v, key, n_perturbs=args.n_perturbs, nmf_iters=args.nmf_iters,
            k_pad=args.k_max, tol=args.tol, chunk=args.fit_chunk,
            warm_start=args.warm_start, mesh=mesh, comm=args.comm,
        )
        sched = ElasticWavefrontScheduler(space, refill=LaneRefillPolicy(order=args.order))
        t0 = time.time()
        result = sched.run(plane)
        dt = time.time() - t0
        extra = {
            "ticks": sched.n_ticks,
            "compiled_shapes": sorted(plane.shapes_compiled),
            "tol": args.tol,
            "fit_chunk": args.fit_chunk,
            "warm_start": args.warm_start,
            "sweeps_run": plane.sweeps_run,
            "sweeps_saved": plane.sweeps_saved,
            "sweeps_fixed_total": plane.sweeps_fixed_total,
            "warm_start_hits": plane.warm_cache.hits,
            "lane_occupancy": plane.last_lane_occupancy,
        }
        if mesh is not None:
            extra["mesh"] = {
                "lanes": plane.lane_count, "data": plane.data_count,
                "v_devices": _device_span(plane.v),
                "pool_devices": _device_span(*plane.pool),
            }
            extra["comm"] = args.comm
        return result, dt, extra
    if args.executor in ("batched", "sharded"):
        if not args.quiet:
            ignored = (
                ("--journal", args.journal),
                ("--distributed-fit", args.distributed_fit),
                ("--order", args.order != "pre"),
                ("--resources", args.resources != ap.get_default("resources")),
            )
            for flag, used in ignored:
                if used:
                    print(f"note: {flag} is ignored by the {args.executor} executor")
        mesh = None
        if args.executor == "sharded":
            mesh = make_wave_mesh(lanes=args.lanes, data=args.data_shards)
        elif args.comm != "sync" and not args.quiet:
            print(f"note: --comm is ignored by the {args.executor} executor")
        plane = NMFkBatchPlane(
            v, key, n_perturbs=args.n_perturbs, nmf_iters=args.nmf_iters,
            k_pad=args.k_max, mesh=mesh, comm=args.comm,
        )
        if (mesh is not None and args.comm == "pipelined"
                and plane.data_count <= 1 and not args.quiet):
            print("note: --comm pipelined is a no-op without --data-shards > 1")
        sched = WavefrontScheduler(space, max_wave=args.max_wave)
        t0 = time.time()
        result = sched.run(plane)
        dt = time.time() - t0
        extra = {"waves": sched.n_dispatches, "compiled_shapes": sorted(plane.shapes_compiled)}
        if mesh is not None:
            extra["mesh"] = {
                "lanes": plane.lane_count, "data": plane.data_count,
                "v_devices": _device_span(plane.v),
            }
            extra["lane_utilization_last"] = plane.last_lane_utilization
            extra["comm"] = args.comm
            if args.comm == "pipelined" and plane.data_count > 1:
                from repro.obs import get_metrics

                extra["overlap_fraction"] = get_metrics().gauge("overlap_fraction")
    else:
        visited: set[int] = set()
        if args.journal:
            coord = FileCoordinator(args.journal)
            bounds, visited = coord.replay(space.selects, space.stops)
            if visited and not args.quiet:
                print(f"restart: {len(visited)} k already journaled, bounds {bounds}")
        else:
            coord = InProcessCoordinator()
        sched = ThreadPoolScheduler(space, args.resources, order=args.order, coordinator=coord)
        t0 = time.time()
        result = sched.run(evaluate, skip=visited)
        dt = time.time() - t0
        extra = {"resources": args.resources}
    return result, dt, extra


def _device_span(*arrays) -> int:
    """Fewest devices any of ``arrays`` is laid out over."""
    return min(len(x.sharding.device_set) for x in arrays)


def _emit(args, result, dt, extra, tracer, metrics) -> dict:
    out = {
        "k_optimal": result.k_optimal,
        "k_true": args.k_true,
        "visited": sorted(result.visited_ks),
        "n_visited": result.n_visited,
        "n_candidates": result.n_candidates,
        "visit_fraction": round(result.visit_fraction, 3),
        "seconds": round(dt, 2),
        "executor": args.executor,
        **extra,
    }
    if args.trace:
        if args.trace.endswith(".jsonl"):
            n_ev = tracer.export_jsonl(args.trace)
        else:
            n_ev = tracer.export_perfetto(args.trace)
        out["trace"] = {"path": args.trace, "events": n_ev}
    if args.metrics:
        summary = metrics.summary()
        payload = {
            "summary": summary,
            "result": {
                "k_optimal": result.k_optimal,
                "n_visited": result.n_visited,
                "n_candidates": result.n_candidates,
                "visit_fraction": result.visit_fraction,
            },
            "seconds": dt,
            "executor": args.executor,
        }
        with open(args.metrics, "w") as f:
            json.dump(payload, f, indent=1)
        out["metrics"] = {"path": args.metrics}
        sf = summary["search"]["visit_fraction"]
        if sf is not None and abs(sf - result.visit_fraction) > 1e-9 and not args.quiet:
            print(f"warning: metrics visit_fraction {sf:.3f} != "
                  f"result {result.visit_fraction:.3f}")
    if not args.quiet:
        print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
