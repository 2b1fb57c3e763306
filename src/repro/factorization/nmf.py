"""Nonnegative Matrix Factorization via multiplicative updates (Frobenius).

The paper's T_model: V (n, m) ≈ W (n, k) H (k, m), W,H >= 0, with the
classic Lee-Seung updates

    H <- H * (W^T V) / (W^T W H + eps)
    W <- W * (V H^T) / (W H H^T + eps)

Two execution paths:
  * ``nmf`` — fully jit'd ``lax.fori_loop`` (fast path for benchmarks).
  * ``nmf_chunked`` — Python loop over jit'd iteration chunks with a
    ``should_abort`` poll between chunks: the paper's §III-D "checks can be
    pushed into the model to terminate such k early" — when another Binary
    Bleed resource prunes this k mid-fit, we stop paying for it. TPU steps
    are not preemptible, so bounded-staleness chunk-granular aborts are the
    TPU-native adaptation.

``use_kernel=True`` routes the H/W updates through the fused Pallas MU
kernel (repro.kernels.nmf_update) — the compute hot spot the paper's
distributed NMF optimizes on GPU, re-tiled for TPU VMEM/MXU.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .batching import batched_lanes

Array = jax.Array
_EPS = 1e-9


class NMFResult(NamedTuple):
    w: Array
    h: Array
    rel_error: Array  # ||V - WH||_F / ||V||_F
    iters: Array


def nmf_init(
    key: Array, n: int, m: int, k: int, v_mean: Array, dtype, k_pad: int | None = None
) -> tuple[Array, Array]:
    """Scaled-uniform W/H init.

    With ``k_pad`` the draw happens at the padded rank and is sliced to k —
    exactly the active block a mask-padded batched fit (``nmf_batched``)
    initializes from for the same key, which is what makes per-k and
    batched fits comparable factor-for-factor.
    """
    kw, kh = jax.random.split(key)
    scale = jnp.sqrt(jnp.maximum(v_mean, _EPS) / k)
    kd = k if k_pad is None else k_pad
    w = scale * jax.random.uniform(kw, (n, kd), dtype, 0.1, 1.0)[:, :k]
    h = scale * jax.random.uniform(kh, (kd, m), dtype, 0.1, 1.0)[:k, :]
    return w, h


_init_wh = nmf_init


def mu_step(v: Array, w: Array, h: Array, use_kernel: bool = False) -> tuple[Array, Array]:
    """One multiplicative-update sweep (H then W)."""
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        h = kernel_ops.mu_update_h(v, w, h)
        w = kernel_ops.mu_update_w(v, w, h)
        return w, h
    wt = w.T
    h = h * (wt @ v) / (wt @ w @ h + _EPS)
    ht = h.T
    w = w * (v @ ht) / (w @ (h @ ht) + _EPS)
    return w, h


@functools.partial(jax.jit, static_argnames=("k", "iters", "use_kernel"))
def nmf(
    v: Array,
    k: int,
    key: Array,
    iters: int = 200,
    use_kernel: bool = False,
    w0: Array | None = None,
    h0: Array | None = None,
) -> NMFResult:
    """Jit'd NMF: fixed iteration count (TPU-friendly, no host sync).

    ``w0``/``h0`` override the random init (both or neither) — used to seed
    a per-k fit with the exact active block of a padded batched init.
    """
    n, m = v.shape
    if (w0 is None) != (h0 is None):
        raise ValueError("pass both w0 and h0, or neither")
    if w0 is None:
        w, h = nmf_init(key, n, m, k, jnp.mean(v), v.dtype)
    else:
        w, h = w0, h0

    def body(_, wh):
        return mu_step(v, *wh, use_kernel=use_kernel)

    w, h = jax.lax.fori_loop(0, iters, body, (w, h))
    err = jnp.linalg.norm(v - w @ h) / jnp.maximum(jnp.linalg.norm(v), _EPS)
    return NMFResult(w, h, err, jnp.asarray(iters))


def _masked_init(v: Array, k_eff: Array, key: Array, k_pad: int) -> tuple[Array, Array]:
    """Masked W/H init at padded rank — the exact draws ``_nmf_masked`` makes.

    Extracted so chunked/elastic fits can start from the same state a
    fixed-iteration masked fit starts from (draw-for-draw).
    """
    n, m = v.shape
    active = jnp.arange(k_pad) < k_eff
    kw, kh = jax.random.split(key)
    scale = jnp.sqrt(jnp.maximum(jnp.mean(v), _EPS) / k_eff)
    w = scale * jax.random.uniform(kw, (n, k_pad), v.dtype, 0.1, 1.0)
    h = scale * jax.random.uniform(kh, (k_pad, m), v.dtype, 0.1, 1.0)
    return w * active[None, :], h * active[:, None]


def _masked_sweeps(
    v: Array,
    w: Array,
    h: Array,
    k_eff: Array,
    k_pad: int,
    sweeps: int,
    use_kernel: bool = False,
    steps: Array | None = None,
) -> tuple[Array, Array, Array]:
    """``sweeps`` masked MU sweeps from (w, h); returns (w, h, rel_error).

    The resumable body shared by ``_nmf_masked`` and the elastic chunked
    executors: running it s1 then s2 sweeps applies the same op sequence as
    one (s1 + s2)-sweep fit, so chunk boundaries are numerically invisible.
    The returned rel_error against ``v`` is the per-chunk convergence signal
    the elastic plane's tol gate consumes.

    ``steps`` (a traced scalar) gates the loop per *call* inside a fixed
    compiled shape: sweep s applies only while ``s < steps``, so a lane
    whose remaining budget is smaller than the chunk advances exactly
    ``steps`` sweeps — bit-identical to a ``steps``-sweep fit — without
    minting a new (chunk-size) compilation.
    """
    active = jnp.arange(k_pad) < k_eff

    def body(s, wh):
        w, h = mu_step(v, *wh, use_kernel=use_kernel)
        w, h = w * active[None, :], h * active[:, None]
        if steps is None:
            return w, h
        live = s < steps
        return jnp.where(live, w, wh[0]), jnp.where(live, h, wh[1])

    with jax.named_scope("mu_update"):
        w, h = jax.lax.fori_loop(0, sweeps, body, (w, h))
    with jax.named_scope("rel_error"):
        err = jnp.linalg.norm(v - w @ h) / jnp.maximum(jnp.linalg.norm(v), _EPS)
    return w, h, err


@functools.partial(jax.jit, static_argnames=("k_pad", "chunk", "use_kernel"))
def _nmf_masked_chunk(
    v: Array, w: Array, h: Array, k_eff: Array, k_pad: int, chunk: int, use_kernel: bool = False
) -> tuple[Array, Array, Array]:
    """Jit'd resumable chunk of a masked fit (the elastic unit of work)."""
    return _masked_sweeps(v, w, h, k_eff, k_pad, chunk, use_kernel=use_kernel)


@functools.partial(jax.jit, static_argnames=("k_pad", "iters", "use_kernel"))
def _nmf_masked(
    v: Array,
    k_eff: Array,
    key: Array,
    k_pad: int,
    iters: int = 200,
    use_kernel: bool = False,
) -> NMFResult:
    """NMF at padded rank k_pad with components >= k_eff zero-masked.

    Lee-Seung updates preserve zeros (H rows / W columns multiply by
    themselves), so masking the init is enough for exactness; we still
    re-mask each sweep to stop eps-sized drift from re-seeding dead
    components over hundreds of iterations.
    """
    w, h = _masked_init(v, k_eff, key, k_pad)
    w, h, err = _masked_sweeps(v, w, h, k_eff, k_pad, iters, use_kernel=use_kernel)
    return NMFResult(w, h, err, jnp.asarray(iters))


def nmf_batched(
    v: Array,
    ks: Sequence[int],
    key: Array,
    k_pad: int | None = None,
    iters: int = 200,
    use_kernel: bool = False,
) -> NMFResult:
    """Fit every rank in ``ks`` as one padded vmapped NMF.

    Returns an NMFResult with a leading batch axis aligned with ``ks``:
    w (b, n, k_pad) / h (b, k_pad, m) with components >= ks[i] zeroed. One
    jit compilation at (k_pad, len(ks)) serves every rank in the wave. Lane
    i reproduces ``nmf(v, ks[i], sub, w0=w0, h0=h0)`` for
    ``sub = fold_in(key, ks[i])`` and ``w0, h0 = nmf_init(sub, n, m, ks[i],
    v.mean(), v.dtype, k_pad=k_pad)``.
    """
    ks_arr, keys, k_pad = batched_lanes(ks, key, k_pad)
    return jax.vmap(
        lambda k_eff, sub: _nmf_masked(v, k_eff, sub, k_pad, iters, use_kernel)
    )(ks_arr, keys)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "use_kernel"))
def _nmf_chunk(v: Array, w: Array, h: Array, k: int, chunk: int, use_kernel: bool) -> tuple[Array, Array]:
    def body(_, wh):
        return mu_step(v, *wh, use_kernel=use_kernel)

    return jax.lax.fori_loop(0, chunk, body, (w, h))


def nmf_chunked(
    v: Array,
    k: int,
    key: Array,
    iters: int = 200,
    chunk: int = 25,
    should_abort: Callable[[], bool] | None = None,
    tol: float | None = None,
    use_kernel: bool = False,
) -> NMFResult:
    """Chunked NMF with §III-D early abort + optional convergence tol.

    Returns partial factors if aborted (callers treat the fit as void).
    """
    n, m = v.shape
    w, h = _init_wh(key, n, m, k, jnp.mean(v), v.dtype)
    v_norm = jnp.linalg.norm(v)
    done = 0
    prev_err = jnp.inf
    while done < iters:
        if should_abort is not None and should_abort():
            break
        step = min(chunk, iters - done)
        w, h = _nmf_chunk(v, w, h, k, step, use_kernel)
        done += step
        if tol is not None:
            err = float(jnp.linalg.norm(v - w @ h) / jnp.maximum(v_norm, _EPS))
            if prev_err - err < tol:
                break
            prev_err = err
    err = jnp.linalg.norm(v - w @ h) / jnp.maximum(v_norm, _EPS)
    return NMFResult(w, h, err, jnp.asarray(done))


def reconstruction_error(v: Array, w: Array, h: Array) -> Array:
    return jnp.linalg.norm(v - w @ h) / jnp.maximum(jnp.linalg.norm(v), _EPS)
