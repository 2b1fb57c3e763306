"""The Binary Bleed paper's planted-rank NMF matrix (section IV-A).

V = W H + noise, nonnegative, where each of the ``k_true`` components owns
a contiguous block of rows and of columns with loadings |N(1, 0.1)| on a
U[0, 0.02] background, and the noise is U[0, ``noise``]. A copy of the
program's ``factorization.synthetic.nmf_data``, kept here so that the
benchmark's data cannot change with the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "m", "k_true", "noise"))
def generate(key, *, n: int, m: int, k_true: int, noise: float):
    """V (n, m) float32, made on the device from ``key``."""
    dtype = jnp.float32
    kw, kh, kn = jax.random.split(key, 3)
    rows_per = n // k_true
    cols_per = m // k_true
    w_bg = jax.random.uniform(kw, (n, k_true), dtype, 0.0, 0.02)
    h_bg = jax.random.uniform(kh, (k_true, m), dtype, 0.0, 0.02)
    row_block = jnp.clip(jnp.arange(n) // max(rows_per, 1), 0, k_true - 1)
    col_block = jnp.clip(jnp.arange(m) // max(cols_per, 1), 0, k_true - 1)
    w_sig = jax.nn.one_hot(row_block, k_true, dtype=dtype)
    h_sig = jax.nn.one_hot(col_block, k_true, dtype=dtype).T
    kw2, kh2 = jax.random.split(kn)
    w = w_bg + w_sig * jnp.abs(1.0 + 0.1 * jax.random.normal(kw2, (n, k_true), dtype))
    h = h_bg + h_sig * jnp.abs(1.0 + 0.1 * jax.random.normal(kh2, (k_true, m), dtype))
    return w @ h + noise * jax.random.uniform(kn, (n, m), dtype)
