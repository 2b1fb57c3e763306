"""One NMFk k-search through the program's normal path, elastic executor.

``binary_bleed_search(plane, k_range, select_threshold, executor="elastic")``
over a fresh ``NMFkElasticPlane`` for every search. The traffic fixes the
search (K, threshold) and NMFk's own definition (perturbations, sweep
budget, noise, padded rank); the plane's scheduling knobs (``tol``,
``chunk``, ``slots``, ``warm_start``) stay at the program's defaults, so a
change of a default is measured as what users get.
"""
from __future__ import annotations


def _plane(v, key, traffic: dict):
    from repro.factorization.planes import NMFkElasticPlane

    return NMFkElasticPlane(
        v, key, n_perturbs=traffic["n_perturbs"], nmf_iters=traffic["nmf_iters"],
        epsilon=traffic["epsilon"], k_pad=traffic["k_pad"],
    )


def run(v, key, traffic: dict):
    from repro.core import binary_bleed_search

    return binary_bleed_search(
        _plane(v, key, traffic), (traffic["k_min"], traffic["k_max"]),
        traffic["select_threshold"], executor="elastic",
    )


def warm(v, key, traffic: dict):
    """One whole search, then the host loop's per-lane reads at every lane
    count: between chunks the plane reads the first ``n_occ`` lanes' errors
    to the host, which JAX compiles once per count, and one search's tail
    need not meet every count. Returns the search's ``k_optimal``."""
    import jax.numpy as jnp

    k_opt = run(v, key, traffic).k_optimal
    slots = _plane(v, key, traffic).slots
    errs = jnp.zeros((slots,), v.dtype)
    for n_occ in range(1, slots + 1):
        [float(e) for e in errs[:n_occ]]
    return k_opt
