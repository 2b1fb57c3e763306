"""The data generators: the same seed makes the same V, another seed another."""
import jax
import numpy as np
import pytest

from chipbench.data import planted_nmf

CASES = [
    {"n": 60, "m": 70, "k_true": 4, "noise": 0.01},
    {"n": 190, "m": 41, "k_true": 20, "noise": 0.01},
]


@pytest.mark.parametrize("params", CASES, ids=["paper_shape", "k20_shape"])
def test_same_seed_same_matrix(params):
    a = np.asarray(planted_nmf.generate(jax.random.PRNGKey(2**31 + 11), **params))
    b = np.asarray(planted_nmf.generate(jax.random.PRNGKey(2**31 + 11), **params))
    c = np.asarray(planted_nmf.generate(jax.random.PRNGKey(5), **params))
    assert a.dtype == np.float32 and (a >= 0).all()
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_planted_copy_follows_the_papers_generator():
    from repro.factorization.synthetic import nmf_data

    key = jax.random.PRNGKey(3)
    ours = planted_nmf.generate(key, n=100, m=110, k_true=8, noise=0.01)
    np.testing.assert_allclose(ours, nmf_data(key, n=100, m=110, k_true=8)[0], atol=1e-5)
