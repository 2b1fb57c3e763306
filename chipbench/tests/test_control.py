"""The control (the reference with float8 operands in every MU product, in
the program's place) comes out not correct against the paper cell's limits,
while the program comes out correct, at a size the CPU holds: the paper's
planted generator at 200 x 220, k_true 5, K = [2, 12], 4 perturbations,
120 sweeps, three seeds, six searches each."""
import jax
import pytest

from chipbench import harness
from chipbench.control import CONTROL_OPERANDS

from .conftest import PAPER_CELL

TRAFFIC = {"search": "nmfk_elastic", "k_min": 2, "k_max": 12, "select_threshold": 0.9,
           "n_perturbs": 4, "nmf_iters": 120, "epsilon": 0.015, "k_pad": 12, "tol": 0.001, "chunk": 25}
SEARCHES = 6


@pytest.fixture(scope="module")
def limits(bench_root):
    import json

    return json.loads((bench_root / "chipbench" / "limits" / f"{PAPER_CELL}.json").read_text())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_where_the_program_passes(bench_root, limits, seed):
    from chipbench.data.planted_nmf import generate
    from chipbench.references import nmfk as reference
    from chipbench.searches import nmfk_elastic as search

    data_key, search_key, _ = harness.seed_keys(seed)
    v = generate(data_key, n=200, m=220, k_true=5, noise=0.01)
    searches = []
    for i in range(SEARCHES):
        key = jax.random.fold_in(search_key, i)
        searches.append(harness.SearchRecord(key, search.run(v, key, TRAFFIC)))
    program = harness.check_searches(searches, v, reference, TRAFFIC)
    control = harness.check_searches(searches, v, reference, TRAFFIC, operands=CONTROL_OPERANDS)
    ok_program, _ = harness.judge(harness.compared_numbers(program, limits["far_gap"]), limits)
    ok_control, _ = harness.judge(harness.compared_numbers(control, limits["far_gap"]), limits)
    assert ok_program
    assert not ok_control
