"""Plain NMFk reference: fixed-iteration Lee-Seung fits and a dense silhouette.

It imports nothing of the program under test. For one search it recomputes
the NMFk score of each k it is asked for:

  1. ``n_perturbs`` copies of V, each multiplied elementwise by
     U[1 - epsilon, 1 + epsilon] noise;
  2. a Frobenius NMF of each copy at rank k by multiplicative updates from
     a scaled-uniform start, H then W in every sweep, run in chunks of
     ``chunk`` sweeps until a chunk improves the relative error
     ||V' - WH|| / ||V'|| against its copy V' by less than ``tol``, or
     ``nmf_iters`` sweeps are spent (NMFk's convergence rule, the one the
     elastic plane applies to its lanes);
  3. the W columns L2-normalised and pooled, grouped by greedy matching of
     each copy's columns to copy 0's (each group gets one column per copy);
  4. the silhouette of every pooled column from the full pooled distance
     matrix, taken in the difference form ``||x - y||``; the score is the
     smallest per-group mean silhouette.

Random draws follow the key schedule the search's keys are defined by:
rank k of a search with key ``key`` uses ``split(fold_in(key, k))`` for its
(noise, start) keys, one of each per copy, and the start is drawn at the
padded rank ``k_pad`` of the traffic and cut to k. So a fit that runs the
full sweep budget from a cold start starts where the reference starts.

Arithmetic is float32 with every matrix product at ``"highest"``
precision. ``operands="float8_e4m3fn"`` instead rounds both operands of
every MU matrix product to that type, each with one scale for the whole
tensor (its largest magnitude maps to the type's largest), and multiplies
at the default precision with float32 accumulation: the control, one step
below the bfloat16 operands the TPU's default precision gives the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MU_EPS = 1e-9  # guard in the multiplicative-update denominators


def lane_keys(search_key, k: int, n_perturbs: int):
    """(noise keys, start keys) of rank k, one per perturbed copy."""
    kp, kf = jax.random.split(jax.random.fold_in(search_key, k))
    return jax.random.split(kp, n_perturbs), jax.random.split(kf, n_perturbs)


def _rounded(x, dtype):
    """x rounded to ``dtype`` under one per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@functools.partial(jax.jit, static_argnames=("k_pad", "iters", "epsilon", "chunk", "tol",
                                             "operands"))
def fit_unit_columns(v, pkeys, fkeys, k, *, k_pad, iters, epsilon, chunk, tol, operands=None):
    """W of every perturbed copy, columns L2-normalised: (p, n, k_pad).

    Rank k is a traced value, so one compiled program serves every k: the
    fit runs at width ``k_pad`` with columns k and above zero from the
    start, and Lee-Seung updates keep a zero column zero (its products
    are exactly zero), so the first k columns are the rank-k fit.
    """
    n, m = v.shape
    active = (jnp.arange(k_pad) < k).astype(v.dtype)
    if operands is None:
        mm = jnp.matmul
    else:
        def mm(a, b):
            return _rounded(a, operands) @ _rounded(b, operands)

    def one(pk, fk):
        vp = v * jax.random.uniform(pk, v.shape, v.dtype, 1.0 - epsilon, 1.0 + epsilon)
        kw, kh = jax.random.split(fk)
        scale = jnp.sqrt(jnp.maximum(jnp.mean(vp), MU_EPS) / k)
        w = scale * jax.random.uniform(kw, (n, k_pad), v.dtype, 0.1, 1.0) * active[None, :]
        h = scale * jax.random.uniform(kh, (k_pad, m), v.dtype, 0.1, 1.0) * active[:, None]
        v_norm = jnp.maximum(jnp.linalg.norm(vp), MU_EPS)

        def sweep(_, wh):
            w, h = wh
            h = h * mm(w.T, vp) / (mm(mm(w.T, w), h) + MU_EPS)
            w = w * mm(vp, h.T) / (mm(w, mm(h, h.T)) + MU_EPS)
            return w, h

        def unconverged(c):
            _, _, done, prev, err = c
            return (done < iters) & ~(prev - err < tol)

        def one_chunk(c):
            w, h, done, _, err = c
            steps = jnp.minimum(chunk, iters - done)
            w, h = jax.lax.fori_loop(0, steps, sweep, (w, h))
            return w, h, done + steps, err, jnp.linalg.norm(vp - mm(w, h)) / v_norm

        # before the first chunk: "improved" from +inf, so it always runs
        start = (w, h, 0, jnp.asarray(jnp.inf, v.dtype), jnp.finfo(v.dtype).max)
        w, *_ = jax.lax.while_loop(unconverged, one_chunk, start)
        return w

    w = jax.vmap(one)(pkeys, fkeys)
    return w / jnp.maximum(jnp.linalg.norm(w, axis=1, keepdims=True), 1e-12)


@jax.jit
def _similarity_and_distances(w):
    """Copy 0's columns against every copy's, and all pooled distances."""
    p, n, k = w.shape
    sims = jnp.einsum("nk,pnj->pkj", w[0], w)
    cols = jnp.transpose(w, (0, 2, 1)).reshape(p * k, n)
    dist = jax.lax.map(lambda c: jnp.sqrt(jnp.sum((cols - c) ** 2, axis=1)), cols)
    return sims, dist


def greedy_groups(sims: np.ndarray) -> np.ndarray:
    """labels (p, k): column j of copy q joins group labels[q, j].

    Repeatedly takes the most similar (reference column, column) pair that
    is still free; each copy gives every group exactly one column.
    """
    p, k, _ = sims.shape
    labels = np.zeros((p, k), np.int64)
    for q in range(p):
        s = np.array(sims[q], np.float64)
        for _ in range(k):
            i, j = np.unravel_index(np.argmax(s), s.shape)
            labels[q, j] = i
            s[i, :] = -np.inf
            s[:, j] = -np.inf
    return labels


def min_group_silhouette(dist: np.ndarray, labels: np.ndarray) -> float:
    """Smallest per-group mean silhouette of the pooled columns."""
    lab = labels.reshape(-1)
    k = int(lab.max()) + 1
    if k == 1:
        return 1.0
    onehot = np.eye(k)[lab]  # (pk, k)
    sizes = onehot.sum(0)
    sums = dist @ onehot  # (pk, k): distance sums to each group
    own = sizes[lab]
    a = sums[np.arange(lab.size), lab] / np.maximum(own - 1, 1)
    mean_to = sums / np.maximum(sizes, 1)
    mean_to[np.arange(lab.size), lab] = np.inf
    b = mean_to.min(1)
    s = (b - a) / np.maximum(np.maximum(a, b), 1e-12)
    s = np.where(own <= 1, 0.0, s)
    return float(min(s[lab == g].mean() for g in range(k)))


def score(v, search_key, k: int, traffic: dict, operands: str | None = None) -> float:
    """The reference NMFk score of rank k for one search (the control's,
    with ``operands`` set)."""
    p, k = traffic["n_perturbs"], int(k)
    pkeys, fkeys = lane_keys(search_key, k, p)
    precision = "highest" if operands is None else "default"
    with jax.default_matmul_precision(precision):
        w = fit_unit_columns(
            v, pkeys, fkeys, k, k_pad=int(traffic["k_pad"]),
            iters=int(traffic["nmf_iters"]), epsilon=float(traffic["epsilon"]),
            chunk=int(traffic["chunk"]), tol=float(traffic["tol"]),
            operands=None if operands is None else jnp.dtype(operands),
        )
        sims, dist = _similarity_and_distances(w)
    sims, dist = jax.device_get((sims, dist))
    # keep the k fitted columns of each copy (pooled row q * k_pad + j)
    keep = (np.arange(p)[:, None] * sims.shape[-1] + np.arange(k)[None, :]).reshape(-1)
    dist = np.asarray(dist, np.float64)[np.ix_(keep, keep)]
    return min_group_silhouette(dist, greedy_groups(np.asarray(sims)[:, :k, :k]))
