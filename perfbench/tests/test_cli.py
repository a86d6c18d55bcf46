"""The command refuses to report without a TPU, and without the program."""
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "pubmed300-steady",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(REPO)
    assert out.returncode == 1, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    out = _run(tmp_path, env)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
