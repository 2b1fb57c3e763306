"""Without a TPU, or without the program, a run prints no result and fails."""
import os
import shutil
import subprocess
import sys

from .conftest import CHECKOUT

ARGS = ["--workload", "nmfk_planted_paper.elastic", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result(tmp_path):
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    os.symlink(CHECKOUT / "src", tmp_path / "src")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench")
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
