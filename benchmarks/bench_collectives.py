"""Pipelined ring collectives: sweep throughput + overlap vs the sync fit.

Acceptance bench for the decomposed-psum MU schedule in
``repro.factorization.distributed``: under 8 virtual CPU devices, run the
same data-sharded NMF fit through both communication schedules and report

  * ``collectives_ring_rel_err`` — ``ring_psum`` (psum_scatter + ring
    all-gather, non-divisible leading dim exercising the pad path) vs
    ``lax.psum`` on the 8-way mesh,
  * ``collectives_sweep_{sync,pipelined}_us`` — measured per-sweep wall
    time of ``distributed_nmf`` under each schedule (the 8 "devices"
    timeshare one core, so this measures schedule overhead, not overlap —
    the pipelined path must not regress it),
  * ``collectives_throughput_ratio`` — sync/pipelined sweep time (>= ~1
    means the decomposed schedule costs nothing even where it cannot win),
  * ``collectives_pipe_rel_err_gap`` — |rel_error difference| of the two
    schedules' fits (the one-sweep-stale staleness bound),
  * ``collectives_overlap_fraction`` / ``collectives_modeled_speedup`` —
    ``overlap_model``'s per-sweep comm-hiding fraction and pipelined-vs-
    sync speedup at the bench shape (the quantity real interconnects
    realize; also published as an ``overlap_fraction`` gauge so the BENCH
    json ``_meta.metrics`` block records it).

Needs 8 XLA devices, so it re-execs itself as a child process with
``--xla_force_host_platform_device_count=8`` (the flag must precede jax
init) and parses one JSON line back — same scaffolding as
``bench_sharded``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD_FLAG = "--child"


def _child_main(full: bool) -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.factorization.distributed import (
        distributed_nmf,
        overlap_model,
        ring_psum,
    )

    devs = jax.devices()
    p = min(8, len(devs))
    mesh = jax.make_mesh((p,), ("data",), devices=devs[:p])
    key = jax.random.PRNGKey(0)

    # --- ring_psum vs lax.psum parity (lead=13 is not divisible by 8) ------
    x = jax.random.normal(key, (p * 4, 13, 33))

    def _reduce(fn):
        f = jax.shard_map(
            lambda xl: fn(xl.reshape(-1, 33)), mesh=mesh,
            in_specs=(P("data"),), out_specs=P(), check_vma=False,
        )
        return jax.jit(f)(x)

    ref = _reduce(lambda v: jax.lax.psum(v, "data"))
    got = _reduce(lambda v: ring_psum(v, "data", p))
    ring_rel_err = float(
        jnp.max(jnp.abs(got - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-12)
    )

    # --- measured sweep throughput, sync vs pipelined ----------------------
    n, m, k = (512, 192, 12) if full else (256, 96, 8)
    iters = 100 if full else 60
    v = jax.random.uniform(jax.random.fold_in(key, 1), (n, m))

    sweep_us = {}
    errs = {}
    for comm in ("sync", "pipelined"):
        distributed_nmf(v, k, key, mesh, iters=iters, comm=comm)  # compile
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            res = distributed_nmf(v, k, key, mesh, iters=iters, comm=comm)
            jax.block_until_ready(res.w)
        sweep_us[comm] = (time.perf_counter() - t0) / reps / iters * 1e6
        errs[comm] = float(res.rel_error)

    model = overlap_model(n, m, k, p)
    return {
        "ring_rel_err": ring_rel_err,
        "sweep_sync_us": sweep_us["sync"],
        "sweep_pipelined_us": sweep_us["pipelined"],
        "throughput_ratio": sweep_us["sync"] / sweep_us["pipelined"],
        "err_sync": errs["sync"],
        "err_pipelined": errs["pipelined"],
        "err_gap": abs(errs["sync"] - errs["pipelined"]),
        "overlap_fraction": model["overlap_fraction"],
        "comm_fraction": model["comm_fraction"],
        "modeled_speedup": model["speedup"],
        "shape": [n, m, k],
        "data_shards": p,
        "iters": iters,
    }


def _spawn_child(full: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo_root, "src"), env.get("PYTHONPATH")) if p
    )
    cmd = [sys.executable, "-m", "benchmarks.bench_collectives", _CHILD_FLAG]
    if full:
        cmd.append("--full")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, cwd=repo_root, env=env, timeout=1800
    )
    if proc.returncode != 0:
        raise RuntimeError(f"collectives bench child failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(quick=True) -> list[tuple[str, float, str]]:
    from repro.obs import get_metrics

    r = _spawn_child(full=not quick)
    # gauge set in the parent (the child's registry dies with it) so the
    # harness's _meta.metrics block records the run's overlap fraction
    get_metrics().set_gauge("overlap_fraction", r["overlap_fraction"])
    n, m, k = r["shape"]
    return [
        (
            "collectives_ring_rel_err",
            r["ring_rel_err"],
            f"ring psum_scatter+gather vs lax.psum, {r['data_shards']} shards "
            "(non-divisible lead exercises padding)",
        ),
        (
            "collectives_sweep_sync_us",
            r["sweep_sync_us"],
            f"measured us/sweep, blocking Gram psums (n={n} m={m} k={k}, "
            f"{r['data_shards']} virtual shards timesharing one core)",
        ),
        (
            "collectives_sweep_pipelined_us",
            r["sweep_pipelined_us"],
            "measured us/sweep, fused scatter+gather with overlapped W-update",
        ),
        (
            "collectives_throughput_ratio",
            r["throughput_ratio"],
            "sync/pipelined sweep time: >= ~1 means no schedule-overhead "
            "regression even where virtual devices cannot overlap",
        ),
        (
            "collectives_pipe_rel_err_gap",
            r["err_gap"],
            f"|rel_error gap| of one-sweep-stale vs sync fit "
            f"(sync {r['err_sync']:.4f}, pipelined {r['err_pipelined']:.4f})",
        ),
        (
            "collectives_overlap_fraction",
            r["overlap_fraction"],
            f"modeled share of per-sweep Gram comm hidden behind the local "
            f"W-update (comm is {r['comm_fraction'] * 100:.1f}% of a sync sweep)",
        ),
        (
            "collectives_modeled_speedup",
            r["modeled_speedup"],
            "modeled pipelined-vs-sync sweep speedup on a balanced interconnect",
        ),
    ]


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        print(json.dumps(_child_main(full="--full" in sys.argv)))
    else:
        for row in run():
            print(row)
