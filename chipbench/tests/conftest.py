"""Shared pieces of the benchmark's own tests (run on the CPU).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests

``bench_root`` is a throwaway checkout root: this benchmark's files, plus
one small cell (``tiny.elastic``: the paper's planted generator at 96 x 104,
k_true 5, K = [2, 12]) judged by the paper cell's own limits, and a peaks
entry for the CPU so the harness can run there.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
for p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "tiny.elastic"
TINY_CONFIG = {"generator": "planted_nmf", "reference": "nmfk",
               "params": {"n": 96, "m": 104, "k_true": 5, "noise": 0.01}}
TINY_TRAFFIC = {"search": "nmfk_elastic", "k_min": 2, "k_max": 12, "select_threshold": 0.9,
                "n_perturbs": 3, "nmf_iters": 100, "epsilon": 0.015, "k_pad": 12, "tol": 0.001, "chunk": 25}
PAPER_CELL = "nmfk_planted_paper.elastic"


def make_root(dest: Path) -> Path:
    """A checkout root at ``dest`` holding the benchmark and the tiny cell."""
    shutil.copytree(CHECKOUT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "tests", "file": "chipbench/configs/tiny.json",
                            "reduced": [], "why": "small enough for the CPU"})
    spec["workloads"].append({"name": TINY, "config": "tiny", "traffic": "tiny", "chips": 1,
                              "why": "small enough for the CPU"})
    for m in spec["per_layer"]:
        m.setdefault("workloads", []).append(TINY)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = dest / "chipbench"
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    shutil.copy(bench / "limits" / f"{PAPER_CELL}.json", bench / "limits" / f"{TINY}.json")
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "tests only"}
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return dest


@pytest.fixture(scope="session", autouse=True)
def compile_cache(tmp_path_factory):
    """Programs the tests compile are cached in a directory of their own."""
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("checkout"))
