"""Compile the main path's kernels for a TPU v5e chip, without the chip.

The TPU compiler is installed with jax, and compiles for a described
topology that is not attached: what Mosaic or XLA:TPU would refuse on the
chip (unaligned blocks, too much VMEM, a program that does not fit HBM)
fails here. Shapes are the paper's: V is 1000 x 1100 (Binary Bleed
§IV-A), the rank is padded to k_pad = 32, and the pooled NMFk columns are
n_perturbs * k_pad = 128 points of width 1000.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and the test
workers each import every test file.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

N, M, K_PAD, P, LANES = 1000, 1100, 32, 4, 8
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(*shape, dtype=f32)`` -> a ShapeDtypeStruct on one v5e chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(*args, **static).compile()


KERNELS = {
    "mu_update_h": (
        lambda v, w, h: ops.mu_update_h(v, w, h, interpret=False),
        lambda s: (s(N, M), s(N, K_PAD), s(K_PAD, M)),
    ),
    "mu_update_w": (
        lambda v, w, h: ops.mu_update_w(v, w, h, interpret=False),
        lambda s: (s(N, M), s(N, K_PAD), s(K_PAD, M)),
    ),
    "silhouette_dist_sums": (
        lambda x, g: ops.silhouette_dist_sums(x, g, interpret=False),
        lambda s: (s(P * K_PAD, N), s(P * K_PAD, K_PAD)),
    ),
    "silhouette_dist_sums_batched": (
        lambda x, g: ops.silhouette_dist_sums_batched(x, g, interpret=False),
        lambda s: (s(LANES, P * K_PAD, N), s(LANES, P * K_PAD, K_PAD)),
    ),
    "pairwise_sq_dists": (
        lambda x: ops.pairwise_sq_dists(x, interpret=False),
        lambda s: (s(P * K_PAD, N),),
    ),
    "pairwise_sq_dists_batched": (
        lambda x: ops.pairwise_sq_dists_batched(x, interpret=False),
        lambda s: (s(LANES, P * K_PAD, N),),
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, spec, no_persistent_cache):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *shapes(spec))
    assert "tpu_custom_call" in compiled.as_text(), f"{name} did not lower to Mosaic"


def _elastic_chunk_args(spec):
    return (
        spec(N, M),  # v
        spec(LANES, N, K_PAD),  # w
        spec(LANES, K_PAD, M),  # h
        spec(LANES, dtype=jnp.int32),  # k_eff
        spec(LANES, dtype=jnp.int32),  # steps
        spec(LANES, 2, dtype=jnp.uint32),  # pkeys
    )


@pytest.mark.parametrize("use_kernel", [True, False], ids=["pallas", "xla"])
def test_elastic_chunk_compiles_for_v5e(use_kernel, spec, no_persistent_cache):
    """The elastic executor's chunk step (8 lanes x 25 sweeps). The kernel
    path must hold Mosaic kernels — the interpret fallback, which the CPU
    backend would pick at trace time, holds none — and either path must
    fit one chip's HBM."""
    from repro.factorization.nmfk import elastic_chunk

    # the code under test asks the process backend (the CPU here) whether
    # to interpret; steer it to the chip's answer
    steer = (
        mock.patch.object(ops, "_interpret_default", lambda: False)
        if use_kernel else contextlib.nullcontext()
    )
    with steer:
        compiled = elastic_chunk.lower(
            *_elastic_chunk_args(spec), k_pad=K_PAD, chunk=25, epsilon=0.015,
            use_kernel=use_kernel,
        ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
