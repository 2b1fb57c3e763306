"""Ring collectives + pipelined MU schedule: properties and regressions.

All tests run on the 1-device runtime: ``jax.vmap`` with an ``axis_name``
gives the collectives (psum, psum_scatter, ppermute, all_gather,
axis_index) real semantics over the mapped axis, so shard-count behaviour
is testable without forcing extra XLA devices. Property tests use
hypothesis (the conftest stub degrades them to seeded sampling when the
real package is absent).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.factorization import distributed
from repro.factorization.distributed import (
    _dnmf_masked_local,
    _mu_sweeps,
    distributed_nmf,
    overlap_model,
    ring_psum,
)


def _over_shards(fn, x_sharded):
    """Run ``fn(x_local)`` on every shard of axis 0 under a named axis."""
    return jax.vmap(fn, axis_name="s")(x_sharded)


# ---------------------------------------------------------------------------
# property: ring psum_scatter + gather == lax.psum
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    lead=st.integers(min_value=1, max_value=17),
    cols=st.integers(min_value=1, max_value=9),
    p=st.sampled_from([1, 2, 3, 4, 8]),
    dtype=st.sampled_from(["float32", "int32"]),
    ppermute=st.sampled_from([False, True]),
)
def test_ring_psum_matches_lax_psum(lead, cols, p, dtype, ppermute):
    # lead is drawn freely so non-multiples of p exercise the pad/trim path
    rng = np.random.default_rng(1_000_003 * lead + 1_009 * cols + 7 * p + ppermute)
    if dtype == "int32":
        x = rng.integers(-9, 9, size=(p, lead, cols)).astype(np.int32)
    else:
        x = rng.standard_normal((p, lead, cols)).astype(np.float32)

    got = _over_shards(lambda xl: ring_psum(xl, "s", p, use_ppermute=ppermute), x)
    ref = _over_shards(lambda xl: jax.lax.psum(xl, "s"), x)

    assert got.shape == ref.shape == x.shape
    if dtype == "int32":
        np.testing.assert_array_equal(got, ref)
    else:
        # float reduction order may differ between the tree psum and the ring
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# property: pipelined (one-sweep-stale) fit stays close to the sync fit
# ---------------------------------------------------------------------------
def _masked_fit_err(v, k_eff, key, k_pad, iters, p, comm):
    n = v.shape[0]
    v_sh = v.reshape(p, n // p, v.shape[1])

    def local(v_l):
        _, err = _dnmf_masked_local(
            v_l, jnp.asarray(k_eff), key, k_pad, iters, "s", n, comm=comm
        )
        return err

    errs = _over_shards(local, v_sh)
    np.testing.assert_allclose(errs, errs[0], rtol=1e-6)  # err is replicated
    return float(errs[0])


@settings(max_examples=6, deadline=None)
@given(
    per=st.sampled_from([6, 8, 12]),  # rows per shard (keeps n divisible by p)
    m=st.sampled_from([12, 20, 28]),
    k=st.integers(min_value=2, max_value=4),
    pad=st.sampled_from([0, 2]),
    p=st.sampled_from([2, 4]),
    seed=st.integers(min_value=0, max_value=99),
)
def test_pipelined_fit_within_staleness_tolerance(per, m, k, pad, p, seed):
    key = jax.random.PRNGKey(seed)
    n = per * p
    w = jax.random.uniform(jax.random.fold_in(key, 1), (n, k))
    h = jax.random.uniform(jax.random.fold_in(key, 2), (k, m))
    v = w @ h

    k_pad = k + pad
    err_sync = _masked_fit_err(v, k, key, k_pad, 60, p, "sync")
    err_pipe = _masked_fit_err(v, k, key, k_pad, 60, p, "pipelined")
    assert np.isfinite(err_sync) and np.isfinite(err_pipe)
    # documented staleness bound (see tests/_conformance_child.py TOL_PIPE)
    assert abs(err_sync - err_pipe) < 5e-2, (err_sync, err_pipe)


def test_pipelined_single_shard_is_exactly_sync():
    """axis_size == 1 has nothing to overlap: the pipelined schedule must
    fall back to the sync sweeps bit-for-bit (same fori_loop program)."""
    key = jax.random.PRNGKey(3)
    v = jax.random.uniform(key, (12, 10))
    mesh = distributed.make_local_mesh(1)
    a = distributed_nmf(v, 3, key, mesh, iters=40, comm="sync")
    b = distributed_nmf(v, 3, key, mesh, iters=40, comm="pipelined")
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.h), np.asarray(b.h))
    assert float(a.rel_error) == float(b.rel_error)


def test_mu_sweeps_rejects_unknown_comm():
    v = jnp.ones((4, 3))
    with pytest.raises(ValueError, match="comm"):
        _mu_sweeps(v, jnp.ones((4, 2)), jnp.ones((2, 3)), None, 5, "s", "async", 2)


# ---------------------------------------------------------------------------
# overlap model sanity
# ---------------------------------------------------------------------------
def test_overlap_model_degenerates_without_data_sharding():
    m = overlap_model(512, 128, 8, data=1)
    assert m["overlap_fraction"] == 0.0
    assert m["comm_fraction"] == 0.0
    assert m["speedup"] == 1.0


def test_overlap_model_bounds_and_speedup():
    for data in (2, 4, 8):
        for balance in (1.0, 8.0, 64.0):
            m = overlap_model(512, 128, 8, data=data, machine_balance=balance)
            assert 0.0 < m["overlap_fraction"] <= 1.0
            assert 0.0 < m["comm_fraction"] < 1.0
            assert 1.0 <= m["speedup"] <= 1.0 / (1.0 - m["comm_fraction"]) + 1e-9
    # compute-rich shapes fully hide the Gram ring
    assert overlap_model(4096, 512, 8, data=4)["overlap_fraction"] == 1.0


def test_shim_unreplicated_path_works_on_live_jax():
    """End-to-end: an unchecked (``check_vma=False``) shard_map with a
    collective runs on the installed jax."""
    mesh = distributed.make_local_mesh(1)
    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False,
    )
    np.testing.assert_allclose(jax.jit(fn)(jnp.arange(4.0)), jnp.arange(4.0))
