"""NMF / NMFk / K-Means / RESCAL substrates + distributed parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scoring import davies_bouldin_score, silhouette_score
from repro.factorization import (
    blob_data,
    distributed_nmf,
    distributed_rescal,
    kmeans,
    make_local_mesh,
    nmf,
    nmf_chunked,
    nmf_data,
    nmfk_score,
    rescal,
    rescal_data,
    rescalk_score,
)

KEY = jax.random.PRNGKey(0)


def test_nmf_monotone_convergence():
    v, _, _ = nmf_data(KEY, n=60, m=66, k_true=4)
    errs = [float(nmf(v, 4, KEY, iters=it).rel_error) for it in (10, 50, 150)]
    assert errs[0] > errs[1] > errs[2]
    assert errs[2] < 0.05


def test_nmf_factors_nonnegative():
    v, _, _ = nmf_data(KEY, n=40, m=44, k_true=3)
    res = nmf(v, 3, KEY, iters=60)
    assert float(jnp.min(res.w)) >= 0.0 and float(jnp.min(res.h)) >= 0.0


def test_nmf_chunked_abort():
    v, _, _ = nmf_data(KEY, n=40, m=44, k_true=3)
    calls = []

    def should_abort():
        calls.append(1)
        return len(calls) >= 3  # abort after 2 chunks

    res = nmf_chunked(v, 3, KEY, iters=200, chunk=20, should_abort=should_abort)
    assert int(res.iters) == 40  # stopped early (§III-D)


def test_nmf_chunked_tol_stops_early():
    v, _, _ = nmf_data(KEY, n=40, m=44, k_true=3)
    res = nmf_chunked(v, 3, KEY, iters=500, chunk=25, tol=1e-5)
    assert int(res.iters) < 500


def test_kmeans_recovers_separated_blobs():
    x, labels_true = blob_data(KEY, n=300, d=4, k_true=4, std=0.3, spread=8.0)
    res = kmeans(x, 4, KEY)
    # cluster-purity via best-match: every true cluster maps to one found one
    purity = 0
    for c in range(4):
        members = np.asarray(res.labels)[np.asarray(labels_true) == c]
        purity += np.bincount(members, minlength=4).max()
    assert purity / len(x.tolist() if hasattr(x, 'tolist') else x) > 0.95


def test_kmeans_inertia_decreases_with_k():
    x, _ = blob_data(KEY, n=200, d=4, k_true=4, spread=6.0)
    i2 = float(kmeans(x, 2, KEY).inertia)
    i6 = float(kmeans(x, 6, KEY).inertia)
    assert i6 < i2


def test_nmfk_square_wave_at_k_true():
    """The paper's core assumption: silhouette high through k_true, cliff after."""
    v, _, _ = nmf_data(KEY, n=80, m=88, k_true=4)
    scores = {
        k: float(nmfk_score(v, k, jax.random.fold_in(KEY, k), n_perturbs=4, nmf_iters=100).min_silhouette)
        for k in (2, 3, 4, 5, 6)
    }
    assert scores[4] > 0.9
    assert scores[5] < 0.5 and scores[6] < 0.5
    assert scores[2] < scores[4] + 1e-6


def test_rescal_convergence():
    x, _, _ = rescal_data(KEY, n_entities=40, n_relations=3, k_true=3)
    res = rescal(x, 3, KEY, iters=120)
    assert float(res.rel_error) < 0.08


def test_rescalk_scores_stable_at_k_true():
    x, _, _ = rescal_data(KEY, n_entities=48, n_relations=3, k_true=4)
    s_true, _ = rescalk_score(x, 4, KEY, n_perturbs=4, iters=100)
    s_over, _ = rescalk_score(x, 7, KEY, n_perturbs=4, iters=100)
    assert float(s_true) > float(s_over)


def test_distributed_nmf_matches_quality():
    v, _, _ = nmf_data(KEY, n=64, m=72, k_true=3)
    mesh = make_local_mesh()
    dist = distributed_nmf(v, 3, KEY, mesh, iters=150)
    serial = nmf(v, 3, KEY, iters=150)
    assert float(dist.rel_error) < 0.05
    assert abs(float(dist.rel_error) - float(serial.rel_error)) < 0.05
    # W reconstructs V with H
    recon = dist.w @ dist.h
    rel = float(jnp.linalg.norm(v - recon) / jnp.linalg.norm(v))
    assert abs(rel - float(dist.rel_error)) < 1e-4


def test_distributed_rescal_quality():
    x, _, _ = rescal_data(KEY, n_entities=40, n_relations=3, k_true=3)
    mesh = make_local_mesh()
    res = distributed_rescal(x, 3, KEY, mesh, iters=100)
    assert float(res.rel_error) < 0.1


def test_scores_prefer_k_true_on_blobs():
    x, _ = blob_data(KEY, n=240, d=5, k_true=4, std=0.4, spread=8.0)
    sil, db = {}, {}
    for k in (2, 4, 8):
        res = kmeans(x, k, KEY)
        sil[k] = float(silhouette_score(x, res.labels, k))
        db[k] = float(davies_bouldin_score(x, res.labels, k))
    assert sil[4] == max(sil.values())
    assert db[4] == min(db.values())


def test_nmfk_own_cluster_sums_match_difference_form():
    """The exact own-cluster sums equal the brute-force difference form
    over each cluster's members, and sit closer to it than the streamed
    Gram form does for near-duplicate columns (the case NMFk scores)."""
    from repro.core.scoring import cluster_dist_sums
    from repro.factorization.nmfk import _align_columns, _own_cluster_dist_sums

    p, n, k = 4, 300, 6
    base = jax.random.uniform(KEY, (n, k))
    noise = 1e-3 * jax.random.normal(jax.random.fold_in(KEY, 1), (p, n, k))
    perm = jnp.stack([jax.random.permutation(jax.random.fold_in(KEY, 2 + q), k) for q in range(p)])
    w_all = jnp.stack([(base + noise[q])[:, perm[q]] for q in range(p)])
    w_all = w_all / jnp.linalg.norm(w_all, axis=1, keepdims=True)
    labels = _align_columns(w_all)
    cols = jnp.transpose(w_all, (0, 2, 1)).reshape(-1, n)

    got = _own_cluster_dist_sums(cols, labels, p)
    x = np.asarray(cols, np.float64)
    lab = np.asarray(labels)
    dist = np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1))
    want = np.array([dist[i, lab == lab[i]].sum() for i in range(len(lab))])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)
    onehot = jax.nn.one_hot(labels, k, dtype=cols.dtype)
    gram = np.asarray(jnp.take_along_axis(cluster_dist_sums(cols, onehot), labels[:, None], 1)[:, 0])
    assert np.max(np.abs(np.asarray(got) - want)) < np.max(np.abs(gram - want))
