"""`swarmbench/run.py` refuses to measure without a TPU, with no fallback to
the CPU, and fails in a directory that holds only the benchmark's own
files (no program to measure). Neither prints a result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "histo_densenet_paper.fedavg_s5", "--seed",
        str(2**31 + 12345), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run([sys.executable, "swarmbench/run.py", *ARGS],
                          cwd=cwd, capture_output=True, text=True, env=env,
                          timeout=300)


def test_run_exits_non_zero_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "swarmbench", tmp_path / "swarmbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
