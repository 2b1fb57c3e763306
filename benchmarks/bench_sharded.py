"""Sharded wavefront executor: wave-throughput vs the single-device plane.

Acceptance bench for the mesh-sharded evaluation plane: run the same
|K|>=31 NMFk search through the batched (single-device) and sharded
(8-lane mesh) executors and report

  * measured wall seconds for both (transparency — on this 1-core CPU
    container the 8 "devices" timeshare one core, so wall clock cannot
    show the parallel win),
  * **modeled wave-throughput speedup** from lane-round accounting, the
    same modeling style as ``bench_distributed``'s modeled_runtime: the
    batched plane fits its padded lanes on one device (lane-slots add up;
    |K|=31 costs 1+2+4+8+16 = 31 slots), the L-lane mesh fits L lanes per
    round (ceil(padded/L) rounds per wave; 8 lanes cost 6 rounds) — with
    one lane-slot's fit time measured from the batched run,
  * k_opt agreement between the two executors,
  * compiled (batch, k_pad) shape counts (bucketing must hold each
    executor's search to a handful of jit shapes; sharded <= 4),
  * modeled scaling over lanes in {1, 2, 4, 8}.

The measurement needs 8 XLA devices, so the bench re-execs itself as a
child process with ``--xla_force_host_platform_device_count=8`` (the flag
must precede jax init — the parent harness has already initialized a
1-device runtime) and parses one JSON line back.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

_CHILD_FLAG = "--child"


def _child_main(full: bool) -> dict:
    import time

    import jax

    from repro.core import WavefrontScheduler, make_space
    from repro.factorization.batching import bucket_batch
    from repro.factorization.planes import NMFkBatchPlane
    from repro.factorization.synthetic import nmf_data

    n, m = (192, 208) if full else (96, 104)
    k_hi = 48 if full else 32
    iters = 100 if full else 60
    key = jax.random.PRNGKey(0)
    v, _, _ = nmf_data(key, n=n, m=m, k_true=5)
    space = make_space((2, k_hi), 0.9)

    class RecordingPlane(NMFkBatchPlane):
        """Keeps the padded size of every dispatch for lane-slot accounting."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.dispatch_sizes: list[int] = []

        def _pad_ks(self, ks):
            padded, k_pad, n_real = super()._pad_ks(ks)
            self.dispatch_sizes.append(len(padded))
            return padded, k_pad, n_real

    def search(mesh):
        plane = RecordingPlane(
            v, key, n_perturbs=3, nmf_iters=iters, k_pad=k_hi, mesh=mesh
        )
        sched = WavefrontScheduler(space)
        t0 = time.perf_counter()
        res = sched.run(plane)
        wall = time.perf_counter() - t0
        return res, plane, sched, wall

    res_b, plane_b, sched_b, wall_b = search(mesh=None)
    lanes = min(8, jax.device_count())
    mesh = jax.make_mesh((lanes, 1), ("lane", "data"), devices=jax.devices()[:lanes])
    res_s, plane_s, sched_s, wall_s = search(mesh=mesh)

    # lane-round accounting: batched = one lane-slot per padded lane;
    # sharded = one round per ceil(padded / lanes)
    slots_b = sum(plane_b.dispatch_sizes)
    rounds_s = sum(math.ceil(sz / lanes) for sz in plane_s.dispatch_sizes)
    slot_s = wall_b / max(slots_b, 1)  # measured per-lane-slot fit seconds

    # modeled scaling: replay the batched search's wave chunk sizes through
    # the bucketing policy at each lane count (the wave trajectory is
    # executor-independent — same scores, same pruning)
    chunks = [len(w.ks) for w in sched_b.waves]
    scaling = {}
    for L in (1, 2, 4, 8):
        compiled: set[int] = set()
        rounds = 0
        for c in chunks:
            b = bucket_batch(c, lanes=L, bucket_min=L, compiled=compiled)
            compiled.add(b)
            rounds += math.ceil(b / L)
        scaling[L] = slots_b / max(rounds, 1)

    return {
        "k_candidates": space.n_candidates if hasattr(space, "n_candidates") else k_hi - 1,
        "k_batched": res_b.k_optimal,
        "k_sharded": res_s.k_optimal,
        "wall_batched_s": wall_b,
        "wall_sharded_s": wall_s,
        "lane_slots_batched": slots_b,
        "lane_rounds_sharded": rounds_s,
        "wave_speedup_modeled": slots_b / max(rounds_s, 1),
        "modeled_batched_s": slot_s * slots_b,
        "modeled_sharded_s": slot_s * rounds_s,
        "shapes_batched": sorted(plane_b.shapes_compiled),
        "shapes_sharded": sorted(plane_s.shapes_compiled),
        "scaling": {str(k): v for k, v in scaling.items()},
        "lanes": lanes,
    }


def _spawn_child(full: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(repo_root, "src"), env.get("PYTHONPATH")) if p
    )
    cmd = [sys.executable, "-m", "benchmarks.bench_sharded", _CHILD_FLAG]
    if full:
        cmd.append("--full")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, cwd=repo_root, env=env, timeout=1800
    )
    if proc.returncode != 0:
        raise RuntimeError(f"sharded bench child failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(quick=True) -> list[tuple[str, float, str]]:
    r = _spawn_child(full=not quick)
    match = float(r["k_batched"] == r["k_sharded"])
    rows = [
        (
            "sharded_wave_speedup_x",
            r["wave_speedup_modeled"],
            f"modeled lane-round speedup at lanes={r['lanes']}: "
            f"{r['lane_slots_batched']} slots -> {r['lane_rounds_sharded']} rounds "
            f"({r['modeled_batched_s']:.1f}s -> {r['modeled_sharded_s']:.1f}s)",
        ),
        (
            "sharded_k_opt_match",
            match,
            f"k_opt batched={r['k_batched']} sharded={r['k_sharded']}",
        ),
        (
            "sharded_shapes_compiled",
            float(len(r["shapes_sharded"])),
            f"distinct (batch, k_pad) jit shapes: {r['shapes_sharded']} "
            f"(batched plane: {len(r['shapes_batched'])})",
        ),
        (
            "sharded_wall_s",
            r["wall_sharded_s"],
            f"measured wall (8 virtual devices timeshare this host's core); "
            f"batched {r['wall_batched_s']:.1f}s",
        ),
    ]
    for L, sp in sorted(r["scaling"].items(), key=lambda kv: int(kv[0])):
        rows.append((f"sharded_scaling_l{L}", sp, "modeled speedup vs single device"))
    return rows


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        print(json.dumps(_child_main(full="--full" in sys.argv)))
    else:
        for row in run():
            print(row)
