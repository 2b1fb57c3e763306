"""The least time the chip could spend on the MU work of one chunk call.

The elastic plane's ``chunk`` span gives, for each dispatch, ``n_occ``
(occupied lanes), ``ks`` (the distinct true ranks among them) and
``sweeps`` (the largest sweep count any lane ran). It gives no per-lane
(k, steps) pairs, so the count is the lower bound these allow:

  * FLOPs: one MU sweep of rank k over V needs W^T V and V H^T, 4 k nnz(V)
    FLOPs. Every occupied lane is counted at the smallest k in ``ks``; one
    lane is known to have run ``sweeps`` sweeps and each other lane at
    least 1, so ``sweeps + n_occ - 1`` lane-sweeps.
  * Bytes: V read once per call, at its least size: min(dense, CSR) with
    ``value_bytes`` per stored value and 4 bytes of column index per
    nonzero. Any implementation reads V at least once per call; one that
    keeps V (or a perturbed copy made on the fly) on chip across sweeps
    need not read it again, so no further reads are counted. Each lane's
    W (n x k) and H (k x m) at the smallest k are read once and written once.
  * Time: the larger of FLOPs over peak FLOP/s and bytes over peak HBM
    bandwidth.

Padding to ``k_pad``, dense work on sparse data and re-reading V each sweep
therefore show as lost share, and no implementation reads over 100%.
"""
from __future__ import annotations


def least_bytes_of_v(n: int, m: int, nnz: int, value_bytes: int = 4) -> int:
    """Fewest bytes that hold V: dense, or CSR values plus column indices."""
    return min(value_bytes * n * m, (value_bytes + 4) * nnz)


def chunk_flops(span: dict, nnz: int) -> float:
    k = min(span["ks"])
    lane_sweeps = span["sweeps"] + span["n_occ"] - 1
    return 4.0 * k * nnz * lane_sweeps


def chunk_bytes(span: dict, n: int, m: int, nnz: int, value_bytes: int = 4) -> float:
    k = min(span["ks"])
    factors = 2.0 * span["n_occ"] * (n + m) * k * value_bytes
    return float(least_bytes_of_v(n, m, nnz, value_bytes)) + factors


def chunk_least_seconds(span: dict, n: int, m: int, nnz: int, peak: dict,
                        value_bytes: int = 4) -> float:
    """Least seconds of one chunk call on a chip with ``peak``'s rates."""
    if span["n_occ"] < 1 or span["sweeps"] < 1:
        return 0.0
    return max(chunk_flops(span, nnz) / peak["flops_per_s"],
               chunk_bytes(span, n, m, nnz, value_bytes) / peak["bytes_per_s"])
