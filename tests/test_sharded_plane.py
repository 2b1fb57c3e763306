"""Mesh-sharded evaluation plane: bucketing, mesh carving, submesh leasing.

The device-heavy parity assertions (sharded vs batched vs the scalar
oracle) need 8 XLA devices, which can only be forced before jax
initializes — they run in a subprocess (``tests/_sharded_child.py``); this
process has a 1-device runtime. Everything shape/policy-level is tested
in-process.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shape bucketing
# ---------------------------------------------------------------------------
def test_bucket_batch_pow2_and_lane_multiple():
    from repro.factorization.batching import bucket_batch

    assert bucket_batch(1) == 1
    assert bucket_batch(3) == 4
    assert bucket_batch(5) == 8
    # lane floor: every dispatch splits evenly over the mesh
    assert bucket_batch(1, lanes=8, bucket_min=8) == 8
    assert bucket_batch(9, lanes=8, bucket_min=8) == 16
    # non-pow2 lane counts still get lane multiples
    assert bucket_batch(7, lanes=6, bucket_min=6) % 6 == 0


def test_bucket_batch_cap_bounds_padding():
    from repro.factorization.batching import bucket_batch

    assert bucket_batch(3, cap=3) == 3
    # cap never undercuts the dispatch itself
    assert bucket_batch(5, cap=3) == 5
    assert bucket_batch(3, lanes=2, bucket_min=2, cap=3) == 4  # lane multiple wins


def test_bucket_batch_reuses_compiled_shapes():
    from repro.factorization.batching import bucket_batch

    # scalar fallback rides the already-compiled 8-bucket instead of
    # minting a batch-of-one executable
    assert bucket_batch(1, lanes=8, bucket_min=8, compiled=[8, 16]) == 8
    assert bucket_batch(9, lanes=8, bucket_min=8, compiled=[16]) == 16
    # fresh target preferred when it is already compiled
    assert bucket_batch(5, lanes=8, bucket_min=8, compiled=[8, 16]) == 8
    # nothing compiled fits -> fresh target
    assert bucket_batch(9, lanes=8, bucket_min=8, compiled=[8]) == 16
    with pytest.raises(ValueError):
        bucket_batch(0)


# ---------------------------------------------------------------------------
# mesh carving + submesh leasing
# ---------------------------------------------------------------------------
def test_make_wave_mesh_single_device():
    from repro.launch.mesh import make_wave_mesh

    mesh = make_wave_mesh()  # 1 CPU device -> (1, 1)
    assert mesh.axis_names == ("lane", "data")
    assert dict(mesh.shape) == {"lane": 1, "data": 1}


def test_make_wave_mesh_validates_device_budget():
    from repro.launch.mesh import make_wave_mesh

    with pytest.raises(ValueError):
        make_wave_mesh(lanes=8)  # needs 8 devices, host has 1
    with pytest.raises(ValueError):
        make_wave_mesh(data=3)  # 1 device does not split into 3 shards
    with pytest.raises(ValueError):
        make_wave_mesh(lanes=0)


def test_submesh_pool_keys_on_worker_not_k():
    """Regression: the distributed-fit executor used ``submeshes[k % n]``,
    so two concurrent workers whose ks collided mod n serialized on one
    device group. The pool leases per worker thread instead."""
    from repro.launch.mesh import SubmeshPool

    subs = [object(), object()]  # pool never touches the mesh itself
    pool = SubmeshPool(subs)
    leases = {}
    barrier = threading.Barrier(2)

    def worker(name, ks):
        barrier.wait()
        got = {pool.acquire() for _ in ks}  # every k, same worker
        assert len(got) == 1  # stable lease across this worker's ks
        leases[name] = got.pop()

    # both workers draw only even ks — k % 2 would land both on subs[0]
    t1 = threading.Thread(target=worker, args=("a", [2, 4, 8]))
    t2 = threading.Thread(target=worker, args=("b", [6, 10, 12]))
    t1.start(); t2.start(); t1.join(); t2.join()
    assert leases["a"] is not leases["b"]
    assert set(pool.assignments().values()) == {0, 1}
    with pytest.raises(ValueError):
        SubmeshPool([])


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------
def test_enable_persistent_cache_configures_jax(tmp_path):
    import jax

    from repro.core import cache_entry_count, enable_persistent_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_persistent_cache(str(tmp_path / "cache")) is True
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")
        assert os.path.isdir(tmp_path / "cache")
        assert cache_entry_count(str(tmp_path / "cache")) == 0
        assert cache_entry_count(str(tmp_path / "missing")) == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


_CACHE_CHILD = (
    "import jax\n"
    "from repro.core import resolve_compile_cache\n"
    "print(resolve_compile_cache())\n"
    "jax.block_until_ready(jax.jit(lambda x: x * 3.0 + 1.0)(jax.numpy.ones(7)))\n"
)


def _cache_child(env_dir: str | None) -> str:
    """Resolve the cache and compile once in a fresh process (the cache
    is initialized once per process); returns the directory it chose."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p
    )
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1]


def test_resolve_compile_cache_keeps_entries_in_env_dir(tmp_path):
    from repro.core import cache_entry_count

    cache = str(tmp_path / "from_env")
    assert _cache_child(cache) == cache
    # a sub-second compile is persisted: the thresholds were lowered
    assert cache_entry_count(cache) > 0


def test_resolve_compile_cache_defaults_to_checkout_root():
    from repro.core import cache_entry_count
    from repro.core.compile_cache import DEFAULT_CACHE_DIR

    assert str(DEFAULT_CACHE_DIR) == os.path.join(os.path.realpath(REPO), ".jax_cache")
    assert _cache_child(None) == str(DEFAULT_CACHE_DIR)
    assert cache_entry_count(str(DEFAULT_CACHE_DIR)) > 0


# ---------------------------------------------------------------------------
# telemetry plumbing
# ---------------------------------------------------------------------------
def test_wavefront_publishes_lane_utilization_gauge():
    from repro.core import WavefrontScheduler, make_space
    from repro.obs import Metrics, use_metrics

    class Plane:
        last_lane_utilization = None

        def evaluate_batch(self, ks):
            self.last_lane_utilization = len(ks) / 8
            return [1.0 if k <= 5 else 0.0 for k in ks]

    metrics = Metrics()
    with use_metrics(metrics):
        WavefrontScheduler(make_space((2, 9), 0.7)).run(Plane())
    util = metrics.gauge("lane_utilization")
    assert util is not None and 0.0 < util <= 1.0


def test_null_tracer_accepts_injected_spans():
    from repro.obs import NULL_TRACER

    NULL_TRACER.add_span("lane", 0.0, 5.0, track="device:3", ks=[2, 4])
    NULL_TRACER.add_event("compile", 0.0, track="device:all")
    assert NULL_TRACER.now_us() == 0.0
    assert NULL_TRACER.events() == []


def test_tracer_now_us_pairs_with_add_span():
    from repro.obs import Tracer

    clock = iter([0.0, 1.0, 2.0])
    t = Tracer(clock=lambda: next(clock))
    t0 = t.now_us()  # 1.0 - 0.0 seconds -> 1e6 us
    t.add_span("lane", t0, t.now_us() - t0, track="device:0", n_real=3)
    (rec,) = t.events()
    assert rec["ts"] == pytest.approx(1e6)
    assert rec["dur"] == pytest.approx(1e6)
    assert rec["track"] == "device:0"


# ---------------------------------------------------------------------------
# device-heavy parity (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------
def test_sharded_parity_under_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_sharded_child.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900,
    )
    assert proc.returncode == 0, f"child failed:\n{proc.stdout}\n{proc.stderr}"
    assert "sharded child OK" in proc.stdout
