"""A configuration, a traffic mix and a per-layer metric added as new files
(plus their ``BENCHMARK.json`` entries) run with no existing file edited."""
import json
import time

from chipbench import harness

from .conftest import make_root

NEW_METRIC = '''
"""Searches completed in the window (a test metric)."""


def read(window):
    return float(len(window.searches))
'''


def test_new_files_make_a_new_cell(tmp_path):
    root = make_root(tmp_path)
    bench = root / "chipbench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "planted_small.json").write_text(json.dumps(
        {"generator": "planted_nmf", "reference": "nmfk",
         "params": {"n": 80, "m": 90, "k_true": 4, "noise": 0.01}}))
    (bench / "traffic" / "ksearch_k2_10.json").write_text(json.dumps(
        {"search": "nmfk_elastic", "k_min": 2, "k_max": 10, "select_threshold": 0.9,
         "n_perturbs": 3, "nmf_iters": 100, "epsilon": 0.015, "k_pad": 10, "tol": 0.001, "chunk": 25}))
    (bench / "metrics" / "searches_in_window.py").write_text(NEW_METRIC)
    (bench / "limits" / "planted_small.k2_10.json").write_text(json.dumps(
        {"limits": {"score_gap": 0.02}, "far_gap": 0.03}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "planted_small", "source": "tests",
                            "file": "chipbench/configs/planted_small.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "planted_small.k2_10", "config": "planted_small",
                              "traffic": "ksearch_k2_10", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "searches_in_window", "unit": "count", "better": "higher",
                              "source": "host_clock", "layer": "bleed", "moves": "search_s",
                              "workloads": ["planted_small.k2_10"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    # every file that was there is as it was
    assert all(p.read_bytes() == b for p, b in before.items())

    cell = harness.load_cell(root, "planted_small.k2_10")
    assert [m["name"] for m in cell.per_layer] == ["searches_in_window"]
    out = harness.run_cell(cell, 7, 0.5, True, time.perf_counter())
    assert out["correct"]
    assert out["metrics"]["searches_in_window"]["value"] == out["attempted"] >= 1
    out = harness.run_cell(cell, 7, 0.5, False, time.perf_counter())
    assert set(out["metrics"]) == {"search_s", "setup_s"}
    assert list(out)[-1] == "compared"
