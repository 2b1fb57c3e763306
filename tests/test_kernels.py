"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


# -----------------------------------------------------------------------------
# NMF MU update
# -----------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,k", [(64, 48, 5), (256, 128, 16), (100, 90, 7), (8, 8, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mu_update_h(n, m, k, dtype):
    kv, kw, kh = jax.random.split(jax.random.fold_in(KEY, n * m + k), 3)
    v = jax.random.uniform(kv, (n, m), dtype)
    w = jax.random.uniform(kw, (n, k), dtype, 0.1, 1.0)
    h = jax.random.uniform(kh, (k, m), dtype, 0.1, 1.0)
    got = ops.mu_update_h(v, w, h)
    want = ref.mu_update_h_ref(v, w, h).astype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("n,m,k", [(64, 48, 5), (256, 128, 16), (100, 90, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mu_update_w(n, m, k, dtype):
    kv, kw, kh = jax.random.split(jax.random.fold_in(KEY, n + m + k), 3)
    v = jax.random.uniform(kv, (n, m), dtype)
    w = jax.random.uniform(kw, (n, k), dtype, 0.1, 1.0)
    h = jax.random.uniform(kh, (k, m), dtype, 0.1, 1.0)
    got = ops.mu_update_w(v, w, h)
    want = ref.mu_update_w_ref(v, w, h).astype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


def test_mu_update_preserves_zero_rows():
    """Zero-padded factor rows must stay zero through the fused update."""
    v = jax.random.uniform(KEY, (32, 24))
    w = jax.random.uniform(KEY, (32, 4), minval=0.1).at[:, -1].set(0.0)
    h = jax.random.uniform(KEY, (4, 24), minval=0.1)
    got = ops.mu_update_w(v, w, h)
    assert float(jnp.max(jnp.abs(got[:, -1]))) == 0.0


# -----------------------------------------------------------------------------
# pairwise distances
# -----------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,d", [(32, 40, 5), (128, 128, 128), (70, 30, 17), (8, 8, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise(n, m, d, dtype):
    kx, ky = jax.random.split(jax.random.fold_in(KEY, n * m * d))
    x = jax.random.normal(kx, (n, d), dtype)
    y = jax.random.normal(ky, (m, d), dtype)
    got = ops.pairwise_sq_dists(x, y)
    want = ref.pairwise_sq_dists_ref(x, y)
    tol = dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


# -----------------------------------------------------------------------------
# flash attention
# -----------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,hq,hk,l,d,window",
    [
        (1, 4, 2, 64, 16, None),   # GQA
        (2, 8, 8, 128, 64, None),  # MHA
        (1, 4, 1, 64, 32, 24),     # MQA + sliding window
        (1, 2, 2, 256, 128, None), # 128-aligned tiles
        (1, 14, 2, 64, 64, None),  # qwen-style 7x group
    ],
)
def test_flash_attention(b, hq, hk, l, d, window):
    ks = jax.random.split(jax.random.fold_in(KEY, hq * l + d), 3)
    q = jax.random.normal(ks[0], (b, hq, l, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hk, l, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hk, l, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.bfloat16)
    got = ops.flash_attention(q, k, v)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )


def test_flash_matches_model_sdpa():
    """Kernel agrees with the model's einsum attention path end to end."""
    from repro.models.attention import _sdpa

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 32, 8, 64), jnp.float32)   # (B, L, H, hd)
    k = jax.random.normal(ks[1], (2, 32, 4, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 32, 4, 64), jnp.float32)
    want = _sdpa(q, k, v, causal=True, window=None)
    got = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_kernel_nmf_path_matches_jnp_path():
    from repro.factorization import nmf, nmf_data

    v, _, _ = nmf_data(KEY, n=64, m=48, k_true=4)
    r1 = nmf(v, 4, KEY, iters=25)
    r2 = nmf(v, 4, KEY, iters=25, use_kernel=True)
    np.testing.assert_allclose(np.asarray(r1.w), np.asarray(r2.w), rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_default_by_backend(backend, interpret, monkeypatch):
    """Interpret on the CPU, Mosaic on the TPU, and no silent interpreter
    on any other backend."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret_default()
    else:
        assert ops._interpret_default() is interpret
