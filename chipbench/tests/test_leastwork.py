"""The least-work count, against cases worked by hand."""
import pytest

from chipbench.leastwork import (chunk_bytes, chunk_flops, chunk_least_seconds,
                                 least_bytes_of_v)

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_dense_v_is_held_dense():
    # 1000 x 1100 with every entry stored: dense 4.4 MB beats CSR 8.8 MB
    assert least_bytes_of_v(1000, 1100, 1_100_000) == 4_400_000


def test_sparse_v_is_held_as_csr():
    # 2% of 18,846 x 4,096 stored: CSR (4 + 4 bytes per nonzero) wins
    nnz = 1_543_864
    assert least_bytes_of_v(18_846, 4_096, nnz) == 8 * nnz


def test_lanes_count_at_the_smallest_rank_and_one_sweep_each_but_one():
    span = {"n_occ": 8, "ks": [5, 9, 12], "sweeps": 25, "batch": 8, "k_pad": 32}
    # 4 k nnz per lane-sweep, k = 5, 25 + 7 lane-sweeps
    assert chunk_flops(span, nnz=1000) == 4 * 5 * 1000 * 32
    # V once as CSR (8 bytes a nonzero beat 4 x 100 x 110 dense), plus 8
    # lanes' W (n x 5) and H (5 x m), each read and written
    assert chunk_bytes(span, n=100, m=110, nnz=1000) == 8 * 1000 + 2 * 8 * (100 + 110) * 5 * 4


def test_a_shared_sweep_reads_v_once():
    one = {"n_occ": 1, "ks": [8], "sweeps": 25}
    eight = {"n_occ": 8, "ks": [8], "sweeps": 25}
    v = least_bytes_of_v(1000, 1100, 1_100_000)
    assert chunk_bytes(one, 1000, 1100, 1_100_000) - v == pytest.approx(
        (chunk_bytes(eight, 1000, 1100, 1_100_000) - v) / 8)


def test_least_time_is_the_larger_bound():
    # paper cell: bytes 4.4e6 + 2*8*2100*8*4 = 5,475,200 -> 6.685 us;
    # flops 4*8*1.1e6*32 = 1.1264e9 -> 5.718 us: bound by bytes
    span = {"n_occ": 8, "ks": [8, 9], "sweeps": 25}
    t = chunk_least_seconds(span, 1000, 1100, 1_100_000, PEAK)
    assert t == pytest.approx(5_475_200 / 819e9)
    # a FLOP-heavy chunk (wide k, many sweeps) is bound by FLOPs
    wide = {"n_occ": 8, "ks": [32], "sweeps": 500}
    t = chunk_least_seconds(wide, 1000, 1100, 1_100_000, PEAK)
    assert t == pytest.approx(4 * 32 * 1_100_000 * 507 / 197e12)


def test_an_empty_chunk_costs_nothing():
    assert chunk_least_seconds({"n_occ": 0, "ks": [], "sweeps": 0}, 10, 10, 100, PEAK) == 0.0
