"""``bench/run.py`` refuses to run without a TPU, and prints no result."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import REPO


def _run(cwd: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "svc-s23p10.mixed-zipf-ie", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    res = _run(REPO)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(str(tmp_path))
    assert res.returncode != 0
    assert res.stdout.strip() == ""
