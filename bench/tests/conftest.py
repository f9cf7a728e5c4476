"""Shared fixtures: a copy of the benchmark, cut to a size the CPU runs.

Run with ``python -m pytest bench/tests`` from the repository root; the
tests use the CPU (``JAX_PLATFORMS=cpu``).
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (REPO, os.path.join(REPO, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: what the tiny copy changes in every traffic file
TINY_TRAFFIC = {"rate": 40, "warm_requests": 1, "trace_offset": 0.2,
                "trace_seconds": 0.5, "check_requests": 48,
                "ingest_block": 256, "advance_edges": 64,
                "check_vertices": 128, "check_hubs": 8, "check_jobs": 2,
                "check_rows": 64}
TINY_SCALE = 9


def _edit(path: str, **changes) -> None:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1)


def make_tiny_root(dest: str) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dest`` at tiny sizes."""
    os.makedirs(dest, exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in os.listdir(os.path.join(dest, "bench", "configs")):
        _edit(os.path.join(dest, "bench", "configs", name),
              scale=TINY_SCALE)
    for name in os.listdir(os.path.join(dest, "bench", "traffic")):
        _edit(os.path.join(dest, "bench", "traffic", name), **TINY_TRAFFIC)
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    """A tiny copy of the benchmark in a temporary directory."""
    return make_tiny_root(str(tmp_path / "checkout"))


def run_tiny(root: str, workload: str, *, seed: int = 2 ** 33 + 7,
             seconds: float = 1.5, trace: bool = False,
             control: bool = False) -> dict:
    """One run of ``workload`` in the tiny copy, on the CPU."""
    from bench import harness
    return harness.run_cell(workload, seed, seconds, trace, root=root,
                            allow_cpu=True, control=control)
