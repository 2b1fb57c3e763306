"""NMFk — automatic model determination for NMF (refs [1]-[3] of the paper).

The scorer Binary Bleed wraps for NMF. For a candidate k:

  1. Create ``n_perturbs`` resampled copies of V (multiplicative uniform
     noise — bootstrap perturbations).
  2. Factorize each: W^(p), H^(p)  (vmapped over perturbations).
  3. Pool all W columns (n_perturbs × k vectors in R^n, L2-normalized) and
     custom-cluster them into k groups by greedy alignment to the medoid
     perturbation (each group holds exactly one column per perturbation —
     the LANL "custom clustering").
  4. Score: silhouette of the pooled columns under those clusters
     (cosine-like geometry via normalized vectors). Stable k ⇒ tight
     ensemble clusters ⇒ silhouette ≈ 1; overfit k ⇒ split/unstable
     components ⇒ silhouette collapses. This is the square-wave signal
     Binary Bleed's pruning assumes.

Returned score is ``min`` cluster silhouette (standard in NMFk: the weakest
component gates stability), along with mean silhouette and relative error.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.scoring import silhouette_samples_masked

from .batching import batched_lanes
from .nmf import _nmf_masked, nmf

Array = jax.Array


class NMFkScore(NamedTuple):
    min_silhouette: Array
    mean_silhouette: Array
    rel_error: Array


def _perturb(key: Array, v: Array, epsilon: float) -> Array:
    """Multiplicative uniform resampling: V ∘ U[1-eps, 1+eps]."""
    return v * jax.random.uniform(key, v.shape, v.dtype, 1.0 - epsilon, 1.0 + epsilon)


def _align_columns(w_all: Array) -> Array:
    """Greedy-match each perturbation's columns to perturbation 0's.

    w_all: (p, n, k) L2-normalized columns. Returns labels (p*k,) grouping
    each pooled column with its matched reference component — a constrained
    clustering where every cluster gets exactly one column per perturbation.
    Greedy argmax over the similarity matrix, masking used columns, is the
    jit-compatible stand-in for Hungarian matching (exact when components
    are well separated, which is the regime the silhouette then measures).
    """
    p, n, k = w_all.shape
    ref = w_all[0]  # (n, k)

    def match_one(w_p):
        sim = ref.T @ w_p  # (k_ref, k_cols)

        def body(_, carry):
            assign, sim_m = carry
            flat = jnp.argmax(sim_m)
            i, j = flat // k, flat % k
            assign = assign.at[j].set(i)
            sim_m = sim_m.at[i, :].set(-jnp.inf).at[:, j].set(-jnp.inf)
            return assign, sim_m

        assign0 = jnp.zeros((k,), jnp.int32)
        assign, _ = jax.lax.fori_loop(0, k, body, (assign0, sim))
        return assign  # column j of w_p belongs to cluster assign[j]

    assigns = jax.vmap(match_one)(w_all)  # (p, k)
    return assigns.reshape(p * k)


@functools.partial(jax.jit, static_argnames=("k", "n_perturbs", "nmf_iters", "use_kernel"))
def nmfk_score(
    v: Array,
    k: int,
    key: Array,
    n_perturbs: int = 8,
    nmf_iters: int = 150,
    epsilon: float = 0.015,
    use_kernel: bool = False,
) -> NMFkScore:
    """Silhouette-stability score of rank k (higher = stable = good)."""
    kp, kf = jax.random.split(key)
    pkeys = jax.random.split(kp, n_perturbs)
    fkeys = jax.random.split(kf, n_perturbs)

    def fit_one(pk, fk):
        vp = _perturb(pk, v, epsilon)
        res = nmf(vp, k, fk, iters=nmf_iters)
        return res.w, res.rel_error

    w_all, errs = jax.vmap(fit_one)(pkeys, fkeys)  # (p, n, k), (p,)
    # L2-normalize columns — NMFk clusters directions, not magnitudes
    w_all = w_all / jnp.maximum(jnp.linalg.norm(w_all, axis=1, keepdims=True), 1e-12)
    labels = _align_columns(w_all)  # (p*k,)
    cols = jnp.transpose(w_all, (0, 2, 1)).reshape(-1, v.shape[0])  # (p*k, n)
    # one streamed dist-sums pass yields both statistics (the pooled-column
    # distance matrix is never materialized on the blocked/Pallas tiers)
    s = silhouette_samples_masked(
        cols, labels, num_clusters=k, use_kernel=use_kernel,
        own_sums=_own_cluster_dist_sums(cols, labels, n_perturbs),
    )
    sil_mean = jnp.mean(s)
    onehot = jax.nn.one_hot(labels, k, dtype=cols.dtype)
    sizes = jnp.sum(onehot, axis=0)
    per_cluster = (onehot.T @ s) / jnp.maximum(sizes, 1.0)
    # guard: k=1 has a single cluster, silhouette undefined -> 1.0 (stable)
    min_sil = jnp.where(k > 1, jnp.min(per_cluster), 1.0)
    sil_mean = jnp.where(k > 1, sil_mean, 1.0)
    return NMFkScore(min_sil, sil_mean, jnp.mean(errs))


def _own_cluster_dist_sums(cols: Array, labels: Array, n_perturbs: int) -> Array:
    """Exact own-cluster distance sums of the pooled columns, (p*k,).

    cols (p*k, n) holds perturbation q's column j at row q*k + j, and the
    alignment makes ``labels`` a permutation of the k clusters within each
    perturbation, so every cluster has exactly one member per perturbation.
    The p x p distances inside each cluster are then cheap enough to take
    in the difference form ``||x - y||``, which stays accurate for the
    near-duplicate columns of a stable rank, where the streamed Gram form
    loses about sqrt(eps) to cancellation (see ``silhouette_samples_masked``).
    """
    p = n_perturbs
    k = cols.shape[0] // p
    x = cols.reshape(p, k, -1)
    lab = labels.reshape(p, k)
    # members[q, c] = perturbation q's column in cluster c
    members = jnp.take_along_axis(x, jnp.argsort(lab, axis=1)[..., None], axis=1)
    diff = members[:, None] - members[None, :]  # (p, p, k, n)
    per_cluster = jnp.sum(jnp.sqrt(jnp.sum(diff * diff, axis=-1)), axis=1)  # (p, k)
    return jnp.take_along_axis(per_cluster, lab, axis=1).reshape(p * k)


def _align_columns_masked(w_all: Array, k_eff: Array) -> Array:
    """``_align_columns`` at padded width: only the first k_eff columns of
    each perturbation participate; padded columns keep their own index as a
    throwaway label (their points are masked out of the scorer)."""
    p, n, k_pad = w_all.shape
    ref = w_all[0]
    valid = jnp.arange(k_pad) < k_eff  # (k_pad,)

    def match_one(w_p):
        sim = ref.T @ w_p  # (k_ref, k_cols)
        sim = jnp.where(valid[:, None] & valid[None, :], sim, -jnp.inf)

        def body(t, carry):
            assign, sim_m = carry
            flat = jnp.argmax(sim_m)
            i, j = flat // k_pad, flat % k_pad
            ok = t < k_eff
            assign = jnp.where(ok, assign.at[j].set(i.astype(jnp.int32)), assign)
            sim_m = jnp.where(ok, sim_m.at[i, :].set(-jnp.inf).at[:, j].set(-jnp.inf), sim_m)
            return assign, sim_m

        assign0 = jnp.arange(k_pad, dtype=jnp.int32)  # padded cols -> own slot
        assign, _ = jax.lax.fori_loop(0, k_pad, body, (assign0, sim))
        return assign

    assigns = jax.vmap(match_one)(w_all)  # (p, k_pad)
    return assigns.reshape(p * k_pad)


def _pooled_w_score(
    w_all: Array,
    errs: Array,
    k_eff: Array,
    k_pad: int,
    n_perturbs: int,
    use_kernel: bool,
) -> NMFkScore:
    """Score a fitted perturbation ensemble: the shared tail of the masked
    scorers. w_all: (p, n, k_pad) raw W factors, errs: (p,) rel errors."""
    active = jnp.arange(k_pad) < k_eff
    with jax.named_scope("align_columns"):
        w_all = w_all / jnp.maximum(jnp.linalg.norm(w_all, axis=1, keepdims=True), 1e-12)
        labels = _align_columns_masked(w_all, k_eff)  # (p*k_pad,)
    cols = jnp.transpose(w_all, (0, 2, 1)).reshape(-1, w_all.shape[1])  # (p*k_pad, n)
    point_mask = jnp.tile(active, n_perturbs)  # (p*k_pad,)
    # one streamed dist-sums pass yields both statistics: mean over active
    # points and NMFk's per-cluster min over active clusters
    with jax.named_scope("silhouette"):
        s = silhouette_samples_masked(
            cols, labels, num_clusters=k_pad, point_mask=point_mask, use_kernel=use_kernel,
            own_sums=_own_cluster_dist_sums(cols, labels, n_perturbs),
        )
    sil_mean = jnp.sum(s) / jnp.maximum(jnp.sum(point_mask), 1.0)
    onehot = jax.nn.one_hot(labels, k_pad, dtype=cols.dtype) * point_mask[:, None]
    sizes = jnp.sum(onehot, axis=0)
    per_cluster = (onehot.T @ s) / jnp.maximum(sizes, 1.0)
    min_sil = jnp.min(jnp.where(active, per_cluster, jnp.inf))
    # k=1: single cluster, silhouette undefined -> 1.0 (stable)
    min_sil = jnp.where(k_eff > 1, min_sil, 1.0)
    sil_mean = jnp.where(k_eff > 1, sil_mean, 1.0)
    return NMFkScore(min_sil, sil_mean, jnp.mean(errs))


@functools.partial(jax.jit, static_argnames=("k_pad", "n_perturbs", "nmf_iters", "use_kernel"))
def _nmfk_score_masked(
    v: Array,
    k_eff: Array,
    key: Array,
    k_pad: int,
    n_perturbs: int = 8,
    nmf_iters: int = 150,
    epsilon: float = 0.015,
    use_kernel: bool = False,
) -> NMFkScore:
    """``nmfk_score`` with the rank padded to k_pad and masked to k_eff.

    All shapes depend only on (k_pad, n_perturbs, nmf_iters), so one jit
    compilation serves every rank in a wavefront batch. At k_eff == k_pad
    the perturbation and init draws coincide with ``nmfk_score``'s.
    """
    kp, kf = jax.random.split(key)
    pkeys = jax.random.split(kp, n_perturbs)
    fkeys = jax.random.split(kf, n_perturbs)

    def fit_one(pk, fk):
        vp = _perturb(pk, v, epsilon)
        res = _nmf_masked(vp, k_eff, fk, k_pad, iters=nmf_iters)
        return res.w, res.rel_error

    w_all, errs = jax.vmap(fit_one)(pkeys, fkeys)  # (p, n, k_pad), (p,)
    return _pooled_w_score(w_all, errs, k_eff, k_pad, n_perturbs, use_kernel)


def _nmfk_score_masked_dist(
    v_l: Array,
    k_eff: Array,
    key: Array,
    k_pad: int,
    data_axis: str,
    n_total: int,
    n_perturbs: int = 8,
    nmf_iters: int = 150,
    epsilon: float = 0.015,
    use_kernel: bool = False,
    comm: str = "sync",
) -> NMFkScore:
    """``_nmfk_score_masked`` with the fit row-distributed over ``data_axis``.

    Runs inside a shard_map body: v_l is this shard's row block. Each
    perturbation draws the *full* (n, m) noise matrix from the replicated
    key and slices its rows, so the fit consumes exactly the draws the
    single-device path consumes; the NMF itself is ``_dnmf_masked_local``
    (pyDNMFk psum structure; ``comm="pipelined"`` overlaps its Gram
    reductions with the local W-update). W is all-gathered (n×k_pad per
    perturbation — tiny next to V) and the pooled-column scoring runs
    replicated.
    """
    from .distributed import _dnmf_masked_local

    n_l, m = v_l.shape
    idx = jax.lax.axis_index(data_axis)
    kp, kf = jax.random.split(key)
    pkeys = jax.random.split(kp, n_perturbs)
    fkeys = jax.random.split(kf, n_perturbs)

    def fit_one(pk, fk):
        noise = jax.random.uniform(
            pk, (n_total, m), v_l.dtype, 1.0 - epsilon, 1.0 + epsilon
        )
        vp_l = v_l * jax.lax.dynamic_slice_in_dim(noise, idx * n_l, n_l, axis=0)
        return _dnmf_masked_local(
            vp_l, k_eff, fk, k_pad, iters=nmf_iters, axis=data_axis,
            n_total=n_total, comm=comm,
        )

    w_all_l, errs = jax.vmap(fit_one)(pkeys, fkeys)  # (p, n_l, k_pad), (p,)
    w_all = jax.lax.all_gather(w_all_l, data_axis, axis=1, tiled=True)  # (p, n, k_pad)
    return _pooled_w_score(w_all, errs, k_eff, k_pad, n_perturbs, use_kernel)


def nmfk_score_batched(
    v: Array,
    ks: Sequence[int],
    key: Array,
    k_pad: int | None = None,
    n_perturbs: int = 8,
    nmf_iters: int = 150,
    epsilon: float = 0.015,
    use_kernel: bool = False,
) -> NMFkScore:
    """Score every rank in ``ks`` as one padded vmapped NMFk ensemble.

    Returns an NMFkScore whose fields carry a leading batch axis aligned
    with ``ks``. Lane i uses ``fold_in(key, ks[i])`` — the same key schedule
    as ``make_nmfk_evaluator`` — so at k_pad == ks[i] the scalar and batched
    scores coincide.
    """
    ks_arr, keys, k_pad = batched_lanes(ks, key, k_pad)
    return jax.vmap(
        lambda k_eff, sub: _nmfk_score_masked(
            v,
            k_eff,
            sub,
            k_pad,
            n_perturbs=n_perturbs,
            nmf_iters=nmf_iters,
            epsilon=epsilon,
            use_kernel=use_kernel,
        )
    )(ks_arr, keys)


@functools.lru_cache(maxsize=64)
def _sharded_score_fn(
    mesh,
    k_pad: int,
    n_perturbs: int,
    nmf_iters: int,
    epsilon: float,
    use_kernel: bool,
    lane_axis: str,
    data_axis: str,
    comm: str = "sync",
):
    """Build (once per config) the jitted shard_map'd wave scorer.

    The returned callable takes ``(ks_arr, keys, v)`` and is cached so every
    wave of the same padded batch shape reuses one compiled executable —
    rebuilding the shard_map per call would defeat the jit cache entirely.
    """
    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    data = shape.get(data_axis, 1)

    if data == 1:
        def body(ks_l, keys_l, v):
            return jax.vmap(
                lambda k_eff, sub: _nmfk_score_masked(
                    v, k_eff, sub, k_pad,
                    n_perturbs=n_perturbs, nmf_iters=nmf_iters,
                    epsilon=epsilon, use_kernel=use_kernel,
                )
            )(ks_l, keys_l)

        in_specs = (P(lane_axis), P(lane_axis, None), P())
    else:
        def body(ks_l, keys_l, v_l):
            n_total = v_l.shape[0] * data
            return jax.vmap(
                lambda k_eff, sub: _nmfk_score_masked_dist(
                    v_l, k_eff, sub, k_pad, data_axis, n_total,
                    n_perturbs=n_perturbs, nmf_iters=nmf_iters,
                    epsilon=epsilon, use_kernel=use_kernel, comm=comm,
                )
            )(ks_l, keys_l)

        in_specs = (P(lane_axis), P(lane_axis, None), P(data_axis, None))

    out_specs = NMFkScore(P(lane_axis), P(lane_axis), P(lane_axis))
    # unchecked: data-sharded scores are replicated over the data axis
    # (all_gather'd W, psum'd errors) but vma inference can't see through the
    # RNG draws, and the column alignment's fori_loop carries unvarying
    # initial values that come back lane-varying
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    ))


def nmfk_score_sharded(
    v: Array,
    ks: Sequence[int],
    key: Array,
    mesh,
    k_pad: int | None = None,
    n_perturbs: int = 8,
    nmf_iters: int = 150,
    epsilon: float = 0.015,
    use_kernel: bool = False,
    lane_axis: str = "lane",
    data_axis: str = "data",
    comm: str = "sync",
) -> NMFkScore:
    """``nmfk_score_batched`` sharded over a 2-D ``Mesh((lane, data))``.

    The wave's k axis is split over ``lane_axis`` (each device group fits a
    disjoint slice of the ensemble); when the mesh has a non-trivial
    ``data_axis``, V's rows are additionally sharded over it and each fit
    runs the pyDNMFk psum structure — the paper's parallel-over-k ×
    distributed-within-k composition in one jit'd dispatch. The key
    schedule is lane i = ``fold_in(key, ks[i])``, identical to the batched
    and scalar paths, so scores agree with ``nmfk_score_batched`` (exactly
    for lane-only meshes; to psum reduction order under data sharding;
    ``comm="pipelined"`` additionally runs the one-sweep-stale overlapped
    Gram schedule inside each data-sharded fit — same ``k_optimal``,
    scores within the conformance suite's documented tolerance).

    Requires len(ks) divisible by the lane count (planes guarantee this by
    bucketing the batch to a lane multiple) and, when data > 1, v's row
    count divisible by the data-axis size.
    """
    from .distributed import COMM_MODES, auto_mesh

    if comm not in COMM_MODES:
        raise ValueError(f"comm must be one of {COMM_MODES}, got {comm!r}")
    mesh = auto_mesh(mesh)
    ks_arr, keys, k_pad = batched_lanes(ks, key, k_pad)
    shape = dict(mesh.shape)
    lanes = shape[lane_axis]
    data = shape.get(data_axis, 1)
    if ks_arr.shape[0] % lanes:
        raise ValueError(
            f"wave size {ks_arr.shape[0]} not divisible by lane count {lanes}"
        )
    if data > 1 and v.shape[0] % data:
        raise ValueError(
            f"v rows {v.shape[0]} not divisible by data-axis size {data}"
        )
    fn = _sharded_score_fn(
        mesh, int(k_pad), int(n_perturbs), int(nmf_iters), float(epsilon),
        bool(use_kernel), lane_axis, data_axis, str(comm),
    )
    return fn(ks_arr, keys, v)


# ---------------------------------------------------------------------------
# elastic lane kernels: chunked convergence-gated fits with warm starts
# ---------------------------------------------------------------------------
# The elastic executor schedules *fit-chunks*, not whole fits: one lane is
# one perturbation fit of one k, advanced ``chunk`` MU sweeps per dispatch.
# The kernels below are the device-side lane lifecycle — cold/warm init,
# resumable chunk (single-device and mesh-sharded), and the pooled-column
# scoring of a completed ensemble. Cold-started lanes are draw-for-draw
# identical to ``_nmfk_score_masked``'s inner fits, so a lane that runs to
# the full sweep budget reproduces the fixed-iteration batched plane's
# factors chunk boundaries notwithstanding.


def elastic_lane_keys(key: Array, k: int, n_perturbs: int) -> tuple[Array, Array]:
    """Per-perturbation (pkeys, fkeys) for k — ``_nmfk_score_masked``'s
    schedule under the planes' ``fold_in(key, k)`` convention."""
    kp, kf = jax.random.split(jax.random.fold_in(key, k))
    return jax.random.split(kp, n_perturbs), jax.random.split(kf, n_perturbs)


@functools.partial(jax.jit, static_argnames=("k_pad", "epsilon"))
def elastic_lane_init(
    v: Array, k_eff: Array, pkey: Array, fkey: Array, k_pad: int, epsilon: float
) -> tuple[Array, Array]:
    """Cold lane init: the exact (W, H) a masked fit of perturbation
    ``pkey`` / init ``fkey`` starts from."""
    from .nmf import _masked_init

    vp = _perturb(pkey, v, epsilon)
    return _masked_init(vp, k_eff, fkey, k_pad)


@functools.partial(jax.jit, static_argnames=("k_pad", "epsilon"))
def elastic_lane_warm_init(
    v: Array,
    k_eff: Array,
    pkey: Array,
    fkey: Array,
    w_src: Array,
    k_src: Array,
    k_pad: int,
    epsilon: float,
) -> tuple[Array, Array]:
    """Warm lane init from a completed neighbor's W (cross-k warm start).

    The first ``min(k_eff, k_src)`` columns of the cold-draw W are replaced
    by the source fit's columns, L2-renormalized to the cold draw's column
    norms so the init's magnitude statistics (and the MU updates' scale
    balance against the fresh H) are preserved; extra columns (k_eff >
    k_src) and H keep their cold draws. Zero source columns fall back to
    the cold draw — a zeroed column is unrecoverable under Lee-Seung.
    """
    from .nmf import _masked_init

    vp = _perturb(pkey, v, epsilon)
    w0, h0 = _masked_init(vp, k_eff, fkey, k_pad)
    take = jnp.arange(k_pad) < jnp.minimum(k_eff, k_src)
    src_norm = jnp.linalg.norm(w_src, axis=0, keepdims=True)
    unit = w_src / jnp.maximum(src_norm, 1e-12)
    tgt_norm = jnp.linalg.norm(w0, axis=0, keepdims=True)
    w = jnp.where((take & (src_norm[0] > 1e-12))[None, :], unit * tgt_norm, w0)
    return w, h0


@functools.partial(jax.jit, static_argnames=("k_pad", "chunk", "epsilon", "use_kernel"))
def elastic_chunk(
    v: Array,
    w: Array,
    h: Array,
    k_eff: Array,
    steps: Array,
    pkeys: Array,
    k_pad: int,
    chunk: int,
    epsilon: float,
    use_kernel: bool = False,
) -> tuple[Array, Array, Array]:
    """Advance a batch of lanes up to ``chunk`` masked MU sweeps (one dispatch).

    w (L, n, k_pad) / h (L, k_pad, m) / k_eff (L,) / steps (L,) / pkeys
    (L, 2). Lane i applies exactly ``steps[i] <= chunk`` sweeps inside the
    fixed compiled shape (lanes near their sweep budget trim their final
    chunk without a fresh compilation). Each lane regenerates its perturbed
    V from its pkey (cheaper than holding L perturbed copies of V in device
    memory) and reports the rel_error against it — the convergence signal
    the tol gate tests host-side. Its ops carry the named scopes
    ``perturb_v``, ``mu_update`` and ``rel_error`` in the profiler's trace.
    """
    from .nmf import _masked_sweeps

    def lane(w_i, h_i, k_i, st, pk):
        with jax.named_scope("perturb_v"):
            vp = _perturb(pk, v, epsilon)
        return _masked_sweeps(
            vp, w_i, h_i, k_i, k_pad, chunk, use_kernel=use_kernel, steps=st
        )

    return jax.vmap(lane)(w, h, k_eff, steps, pkeys)


@functools.lru_cache(maxsize=64)
def _elastic_chunk_sharded_fn(
    mesh,
    k_pad: int,
    chunk: int,
    epsilon: float,
    use_kernel: bool,
    lane_axis: str,
    data_axis: str,
    comm: str,
):
    """Build (once per config) the jitted shard_map'd elastic chunk step.

    Lanes split over ``lane_axis``; with a non-trivial ``data_axis`` each
    lane's rows (of both V and its W block) are additionally sharded and
    the chunk runs the psum'd Gram structure of ``_dnmf_masked_chunk_local``
    — the convergence residual is assembled from the same psums, so the tol
    gate under data sharding costs one scalar all-reduce pair per chunk.
    """
    from jax.sharding import PartitionSpec as P

    from .distributed import _dnmf_masked_chunk_local
    from .nmf import _masked_sweeps

    shape = dict(mesh.shape)
    data = shape.get(data_axis, 1)

    if data == 1:
        def body(w_b, h_b, k_b, st_b, pk_b, v):
            def lane(w_i, h_i, k_i, st, pk):
                vp = _perturb(pk, v, epsilon)
                return _masked_sweeps(
                    vp, w_i, h_i, k_i, k_pad, chunk, use_kernel=use_kernel, steps=st
                )

            return jax.vmap(lane)(w_b, h_b, k_b, st_b, pk_b)

        in_specs = (
            P(lane_axis), P(lane_axis), P(lane_axis), P(lane_axis), P(lane_axis, None), P(),
        )
        out_specs = (P(lane_axis), P(lane_axis), P(lane_axis))
    else:
        def body(w_b, h_b, k_b, st_b, pk_b, v_l):
            n_l, m = v_l.shape
            n_total = n_l * data
            idx = jax.lax.axis_index(data_axis)

            def lane(w_l, h_l, k_i, st, pk):
                noise = jax.random.uniform(
                    pk, (n_total, m), v_l.dtype, 1.0 - epsilon, 1.0 + epsilon
                )
                vp_l = v_l * jax.lax.dynamic_slice_in_dim(noise, idx * n_l, n_l, axis=0)
                return _dnmf_masked_chunk_local(
                    vp_l, w_l, h_l, k_i, k_pad, chunk, data_axis, data,
                    comm=comm, steps=st,
                )

            return jax.vmap(lane)(w_b, h_b, k_b, st_b, pk_b)

        in_specs = (
            P(lane_axis, data_axis), P(lane_axis), P(lane_axis), P(lane_axis),
            P(lane_axis, None), P(data_axis, None),
        )
        # h and err are replicated over data (psum'd Grams / residual) but
        # the RNG draws defeat replication inference
        out_specs = (P(lane_axis, data_axis), P(lane_axis), P(lane_axis))

    # unchecked, as in _sharded_score_fn
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    ))


def elastic_chunk_sharded(
    v: Array,
    w: Array,
    h: Array,
    k_eff: Array,
    steps: Array,
    pkeys: Array,
    mesh,
    k_pad: int,
    chunk: int,
    epsilon: float,
    use_kernel: bool = False,
    lane_axis: str = "lane",
    data_axis: str = "data",
    comm: str = "sync",
) -> tuple[Array, Array, Array]:
    """``elastic_chunk`` sharded over a 2-D ``Mesh((lane, data))``.

    Requires the lane batch divisible by the lane count and, when data > 1,
    v's rows divisible by the data-axis size (the elastic plane's slot
    bucketing guarantees the former).
    """
    from .distributed import auto_mesh

    mesh = auto_mesh(mesh)
    lanes = dict(mesh.shape)[lane_axis]
    if w.shape[0] % lanes:
        raise ValueError(f"lane batch {w.shape[0]} not divisible by lane count {lanes}")
    fn = _elastic_chunk_sharded_fn(
        mesh, int(k_pad), int(chunk), float(epsilon), bool(use_kernel),
        lane_axis, data_axis, str(comm),
    )
    return fn(w, h, k_eff, steps, pkeys, v)


@functools.partial(jax.jit, static_argnames=("k_pad", "n_perturbs", "use_kernel"))
def elastic_pooled_score(
    w_all: Array,
    errs: Array,
    k_eff: Array,
    k_pad: int,
    n_perturbs: int,
    use_kernel: bool = False,
) -> NMFkScore:
    """Score a completed lane ensemble (p, n, k_pad) — the shared pooled-
    column silhouette tail, jitted once per (k_pad, n_perturbs). Its ops
    carry the named scopes ``align_columns`` and ``silhouette``."""
    return _pooled_w_score(w_all, errs, k_eff, k_pad, n_perturbs, use_kernel)


def make_nmfk_evaluator(
    v: Array,
    key: Array,
    n_perturbs: int = 8,
    nmf_iters: int = 150,
    epsilon: float = 0.015,
    statistic: str = "min",
    use_kernel: bool = False,
) -> Callable[[int], float]:
    """Binary Bleed ``evaluate(k)`` closure over a dataset."""

    def evaluate(k: int, should_abort=None) -> float:
        del should_abort  # jit'd fast path has no chunk boundary to poll
        sub = jax.random.fold_in(key, k)
        sc = nmfk_score(
            v,
            int(k),
            sub,
            n_perturbs=n_perturbs,
            nmf_iters=nmf_iters,
            epsilon=epsilon,
            use_kernel=use_kernel,
        )
        return float(sc.min_silhouette if statistic == "min" else sc.mean_silhouette)

    return evaluate
