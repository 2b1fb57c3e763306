"""Cluster-quality scoring in pure JAX (jit-compatible, Pallas-accelerable).

The paper pairs Binary Bleed with:
  * silhouette score (maximize) — NMFk / RESCALk stability scoring,
  * Davies-Bouldin index (minimize) — K-Means.

Both reduce all-pairs distances — the Tscorer hot spot. The silhouette only
ever consumes the (n, n) distance matrix through one contraction,
``dist_sums = sqrt(D2) @ onehot`` — so ``cluster_dist_sums`` computes the
(n, k) sums directly and dispatches across three tiers:

  1. **dense jnp** — materialize sqrt(D2) and contract. Fastest for small n
     (one fused XLA GEMM chain), O(n^2) memory; selected when the per-lane
     distance block fits ``_DENSE_MAX_ELEMENTS``.
  2. **blocked jnp** — ``lax.map`` over row blocks: each (block_rows, n)
     distance strip is built, contracted to (block_rows, k), and freed.
     Peak footprint O(block_rows * n) instead of O(n^2); serves large n on
     any backend and every non-tile-aligned shape.
  3. **Pallas** (``use_kernel=True``) — the fused streaming kernel
     (`repro.kernels.silhouette_sums`): each (bn, bm) distance tile lives
     only in VMEM, sqrt applied in-register, accumulated straight into the
     (bn, k) sums. HBM output traffic O(n*k); D never exists in HBM.

``pairwise_sq_dists`` likewise dispatches to the Pallas distance kernel
(`repro.kernels.pairwise_dist`) when ``use_kernel=True``; the jnp fallbacks
are the oracles the kernels are tested against.

§III-D synthetic score models (square wave / Laplacian peak) are included:
they drive the property tests and the visit-count benchmarks without paying
for real fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array


def pairwise_sq_dists(x: Array, y: Array | None = None, use_kernel: bool = False) -> Array:
    """Squared euclidean distances between rows of x (..., n, d) and y (..., m, d).

    Leading batch axes broadcast; with ``use_kernel=True`` a 2-D input goes
    to the tiled Pallas kernel and a 3-D input to its batched (leading-axis)
    entry point, so the Pallas path stays usable from batched scorers.
    """
    y = x if y is None else y
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        # the kernels take equal-rank operands; materialize the broadcast
        # the jnp path would do implicitly for mixed 2-D/3-D inputs
        if x.ndim == 2 and y.ndim == 3:
            x = jnp.broadcast_to(x, (y.shape[0],) + x.shape)
        elif x.ndim == 3 and y.ndim == 2:
            y = jnp.broadcast_to(y, (x.shape[0],) + y.shape)
        if x.ndim == 2:
            return kernel_ops.pairwise_sq_dists(x, y)
        if x.ndim == 3:
            return kernel_ops.pairwise_sq_dists_batched(x, y)
        raise ValueError(f"kernel path supports 2-D or 3-D inputs, got {x.ndim}-D")
    # ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y  with clamping for fp error
    xx = jnp.sum(x * x, axis=-1)[..., :, None]
    yy = jnp.sum(y * y, axis=-1)[..., None, :]
    d2 = xx + yy - 2.0 * jnp.matmul(x, jnp.swapaxes(y, -1, -2))
    return jnp.maximum(d2, 0.0)


# Dense-tier ceiling: largest per-lane (n, m) distance block the dense path
# may materialize (fp32 elements; 2048^2 = 16 MiB). Above it, row-blocking.
_DENSE_MAX_ELEMENTS = 2048 * 2048
_DEFAULT_BLOCK_ROWS = 512


def _cluster_dist_sums_blocked(x: Array, onehot: Array, block_rows: int) -> Array:
    """Tier 2: row-blocked ``sqrt(pairwise) @ onehot`` via ``lax.map``.

    x (..., n, d), onehot (..., n, k) — each (block_rows, n) distance strip
    is contracted to (block_rows, k) and discarded, so the peak footprint is
    O(block_rows * n) regardless of n.
    """
    n = x.shape[-2]
    n_blocks = -(-n // block_rows)
    pad = n_blocks * block_rows - n
    widths = [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)]
    xp = jnp.pad(x, widths)

    def one_block(i):
        xi = jax.lax.dynamic_slice_in_dim(xp, i * block_rows, block_rows, axis=-2)
        strip = jnp.sqrt(pairwise_sq_dists(xi, x))  # (..., block_rows, n)
        return jnp.matmul(strip, onehot)

    res = jax.lax.map(one_block, jnp.arange(n_blocks))  # (n_blocks, ..., block_rows, k)
    res = jnp.moveaxis(res, 0, -3)  # (..., n_blocks, block_rows, k)
    res = res.reshape(res.shape[:-3] + (n_blocks * block_rows, onehot.shape[-1]))
    return res[..., :n, :]


def cluster_dist_sums(
    x: Array,
    onehot: Array,
    use_kernel: bool = False,
    block_rows: int | None = None,
) -> Array:
    """(…, n, k) sums of sqrt distances from every point to every cluster.

    ``out[..., i, c] = sum_j sqrt(||x_i - x_j||^2) * onehot[..., j, c]`` —
    the only form in which the silhouette consumes the distance matrix.
    Masked points carry zero one-hot rows and contract to nothing.

    Dispatch (see module docstring): ``use_kernel=True`` routes 2-D inputs
    to the fused streaming Pallas kernel and 3-D inputs to its batched
    entry; otherwise small problems take the dense jnp tier and anything
    past ``_DENSE_MAX_ELEMENTS`` per lane the blocked tier. Passing
    ``block_rows`` forces the blocked tier at that strip height.
    """
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        # the kernels take equal-rank operands; the jnp tiers instead keep
        # an unbatched x unbatched so one distance pass serves all lanes
        if x.ndim == onehot.ndim - 1:
            x = jnp.broadcast_to(x, onehot.shape[:-2] + x.shape[-2:])
        elif onehot.ndim == x.ndim - 1:
            onehot = jnp.broadcast_to(onehot, x.shape[:-2] + onehot.shape[-2:])
        if x.ndim == 2:
            return kernel_ops.silhouette_dist_sums(x, onehot)
        if x.ndim == 3:
            return kernel_ops.silhouette_dist_sums_batched(x, onehot)
        raise ValueError(f"kernel path supports 2-D or 3-D inputs, got {x.ndim}-D")
    n = x.shape[-2]
    if block_rows is None and n * n <= _DENSE_MAX_ELEMENTS:
        return jnp.matmul(jnp.sqrt(pairwise_sq_dists(x)), onehot)
    return _cluster_dist_sums_blocked(x, onehot, block_rows or _DEFAULT_BLOCK_ROWS)


@functools.partial(jax.jit, static_argnames=("num_clusters", "use_kernel"))
def silhouette_score(x: Array, labels: Array, num_clusters: int, use_kernel: bool = False) -> Array:
    """Mean silhouette coefficient, vectorized over clusters.

    Matches sklearn semantics: singleton clusters get s(i)=0; requires
    ``num_clusters`` static for fixed shapes under jit.
    """
    n = x.shape[0]
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=x.dtype)  # (n, k)
    sizes = jnp.sum(onehot, axis=0)  # (k,)
    # sum of distances from each point to each cluster: (n, k) — streamed,
    # the (n, n) distance matrix is never materialized past the dense tier
    dist_sums = cluster_dist_sums(x, onehot, use_kernel=use_kernel)
    own_size = sizes[labels]  # (n,)
    # a(i): mean intra-cluster distance excluding self
    a = dist_sums[jnp.arange(n), labels] / jnp.maximum(own_size - 1.0, 1.0)
    # b(i): min over other clusters of mean distance
    mean_to = dist_sums / jnp.maximum(sizes[None, :], 1.0)  # (n, k)
    mask_own = jax.nn.one_hot(labels, num_clusters, dtype=bool)
    empty = (sizes[None, :] == 0)
    big = jnp.asarray(jnp.inf, x.dtype)
    b = jnp.min(jnp.where(mask_own | empty, big, mean_to), axis=1)
    s = (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-12)
    s = jnp.where(own_size <= 1.0, 0.0, s)  # singleton convention
    return jnp.mean(s)


@functools.partial(jax.jit, static_argnames=("num_clusters",))
def davies_bouldin_score(x: Array, labels: Array, num_clusters: int) -> Array:
    """Davies-Bouldin index (lower = better separated clusters)."""
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=x.dtype)  # (n, k)
    sizes = jnp.maximum(jnp.sum(onehot, axis=0), 1.0)  # (k,)
    centroids = (onehot.T @ x) / sizes[:, None]  # (k, d)
    # intra-cluster scatter S_i: mean distance to centroid
    d_to_c = jnp.sqrt(pairwise_sq_dists(x, centroids))  # (n, k)
    own_d = jnp.sum(d_to_c * onehot, axis=1)  # (n,)
    scatter = (onehot.T @ own_d) / sizes  # (k,)
    # centroid separation M_ij
    m = jnp.sqrt(pairwise_sq_dists(centroids))  # (k, k)
    r = (scatter[:, None] + scatter[None, :]) / jnp.maximum(m, 1e-12)
    r = jnp.where(jnp.eye(num_clusters, dtype=bool), -jnp.inf, r)
    # empty clusters contribute nothing
    present = jnp.sum(onehot, axis=0) > 0
    r = jnp.where(present[None, :], r, -jnp.inf)
    worst = jnp.max(r, axis=1)
    worst = jnp.where(present, worst, 0.0)
    return jnp.sum(worst) / jnp.maximum(jnp.sum(present), 1.0)


# --------------------------------------------------------------------------
# Masked variants — padded batched fits (one vmapped fit serves many k's)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("num_clusters", "use_kernel"))
def silhouette_samples_masked(
    x: Array,
    labels: Array,
    num_clusters: int,
    point_mask: Array | None = None,
    use_kernel: bool = False,
    own_sums: Array | None = None,
) -> Array:
    """Per-point silhouette values; padding points and clusters are zeroed.

    Shapes are axis-agnostic over optional leading batch dims: x (..., n, d),
    labels (..., n) int, point_mask (..., n) bool (False = padding point,
    excluded from every cluster; its s(i) is 0). Clusters that end up empty
    after masking — in particular the padded slots >= k_eff of a mask-padded
    fit — never appear in b(i) and contribute nothing. Returns s (..., n);
    both the mean score and NMFk's per-cluster min reduce from this one
    streamed dist-sums pass.

    ``own_sums`` (..., n), when given, replaces each point's own-cluster
    distance sum. The streamed sums come from ``||x||^2 + ||y||^2 - 2 x.y``,
    whose absolute error is about eps * ||x||^2; the sqrt turns that into
    an error of about sqrt(eps) for near-duplicate points, which is exactly
    what a tight cluster holds. A caller that can afford the difference
    form for the own cluster (NMFk: p members per cluster) passes it here.
    """
    mask = (
        jnp.ones(x.shape[:-1], bool)
        if point_mask is None
        else (jnp.zeros(x.shape[:-1], bool) | point_mask)
    )
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=x.dtype) * mask[..., None]
    sizes = jnp.sum(onehot, axis=-2)  # (..., k) — active members only
    # masked one-hot rows are zero, so the streaming contraction is exact:
    # padding points contribute nothing without ever masking distances
    dist_sums = cluster_dist_sums(x, onehot, use_kernel=use_kernel)  # (..., n, k)
    own_size = jnp.take_along_axis(sizes[..., None, :], labels[..., None], axis=-1)[..., 0]
    if own_sums is None:
        own_sums = jnp.take_along_axis(dist_sums, labels[..., None], axis=-1)[..., 0]
    a = own_sums / jnp.maximum(own_size - 1.0, 1.0)
    mean_to = dist_sums / jnp.maximum(sizes[..., None, :], 1.0)
    mask_own = jax.nn.one_hot(labels, num_clusters, dtype=bool)
    empty = sizes[..., None, :] == 0  # includes every padded cluster slot
    big = jnp.asarray(jnp.inf, x.dtype)
    b = jnp.min(jnp.where(mask_own | empty, big, mean_to), axis=-1)
    s = (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-12)
    s = jnp.where(own_size <= 1.0, 0.0, s)  # singleton convention
    return jnp.where(mask, s, 0.0)


@functools.partial(jax.jit, static_argnames=("num_clusters", "use_kernel"))
def silhouette_score_masked(
    x: Array,
    labels: Array,
    num_clusters: int,
    point_mask: Array | None = None,
    use_kernel: bool = False,
) -> Array:
    """Mean silhouette over active points only; padded clusters are ignored.

    The score at (k_eff, k_pad) equals ``silhouette_score`` at k_eff; see
    ``silhouette_samples_masked`` for the shape/mask contract.
    """
    s = silhouette_samples_masked(x, labels, num_clusters, point_mask, use_kernel)
    if point_mask is None:
        return jnp.mean(s, axis=-1)
    n_active = jnp.sum(jnp.zeros(x.shape[:-1], bool) | point_mask, axis=-1)
    return jnp.sum(s, axis=-1) / jnp.maximum(n_active, 1.0)


@functools.partial(jax.jit, static_argnames=("num_clusters",))
def davies_bouldin_score_masked(
    x: Array,
    labels: Array,
    num_clusters: int,
    cluster_mask: Array | None = None,
    point_mask: Array | None = None,
) -> Array:
    """Davies-Bouldin index ignoring padded clusters (and padding points).

    Axis-agnostic over leading batch dims like ``silhouette_score_masked``.
    ``cluster_mask`` (..., k) marks the active centroid slots of a
    mask-padded fit (slots >= k_eff are False); inactive or empty clusters
    are excluded from both the pairwise-worst max and the final mean.
    """
    mask = (
        jnp.ones(x.shape[:-1], bool) if point_mask is None else jnp.broadcast_to(point_mask, x.shape[:-1])
    )
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=x.dtype) * mask[..., None]
    if cluster_mask is not None:
        onehot = onehot * cluster_mask[..., None, :].astype(x.dtype)
    counts = jnp.sum(onehot, axis=-2)  # (..., k)
    sizes = jnp.maximum(counts, 1.0)
    centroids = jnp.matmul(jnp.swapaxes(onehot, -1, -2), x) / sizes[..., None]
    d_to_c = jnp.sqrt(pairwise_sq_dists(x, centroids))  # (..., n, k)
    own_d = jnp.sum(d_to_c * onehot, axis=-1)  # (..., n)
    scatter = jnp.matmul(jnp.swapaxes(onehot, -1, -2), own_d[..., None])[..., 0] / sizes
    m = jnp.sqrt(pairwise_sq_dists(centroids))  # (..., k, k)
    r = (scatter[..., :, None] + scatter[..., None, :]) / jnp.maximum(m, 1e-12)
    r = jnp.where(jnp.eye(num_clusters, dtype=bool), -jnp.inf, r)
    present = counts > 0
    if cluster_mask is not None:
        present = present & cluster_mask
    r = jnp.where(present[..., None, :], r, -jnp.inf)
    worst = jnp.max(r, axis=-1)
    worst = jnp.where(present, worst, 0.0)
    return jnp.sum(worst, axis=-1) / jnp.maximum(jnp.sum(present, axis=-1), 1.0)


# --------------------------------------------------------------------------
# §III-D synthetic score distributions
# --------------------------------------------------------------------------
def square_wave_score(k: int | Array, k_optimal: int, hi: float = 1.0, lo: float = 0.0) -> Array:
    """S(k) = (sgn(k0 - k) + 1)/2 scaled to [lo, hi] — ideal silhouette shape.

    Follows the paper: +1 for k < k0+1 (i.e. k <= k0), -1 after — high
    scores up to and including the optimum, a cliff after it.
    """
    k = jnp.asarray(k)
    s01 = (jnp.sign(k_optimal - k + 0.5) + 1.0) / 2.0
    return lo + (hi - lo) * s01


def laplacian_score(k: int | Array, k_optimal: int, width: float = 2.0, hi: float = 1.0) -> Array:
    """Worst-case §III-D distribution: a Laplacian peak at k0.

    Only k≈k0 crosses a high threshold; Binary Bleed degrades gracefully to
    at-most-linear visits.
    """
    k = jnp.asarray(k, jnp.float32)
    return hi * jnp.exp(-jnp.abs(k - k_optimal) / width)


def noisy(score_fn, key: jax.Array, sigma: float = 0.02):
    """Wrap a synthetic score with Gaussian observation noise."""

    def f(k):
        sub = jax.random.fold_in(key, int(k))
        return score_fn(k) + sigma * jax.random.normal(sub, ())

    return f
