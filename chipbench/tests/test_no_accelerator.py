"""The command refuses to measure without a TPU, and without the
program beside it."""

import os
import shutil
import subprocess
import sys

from chipbench import run

ARGS = ["-m", "chipbench.run", "--workload", "sf19_ecmp.perm4m",
        "--seed", "2147483659", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_exits_nonzero_and_prints_no_result_without_a_tpu():
    p = subprocess.run([sys.executable] + ARGS, cwd=run.ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr and "cpu" in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable] + ARGS, cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
