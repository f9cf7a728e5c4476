"""Paper Figures 4/5/6: scaling.

Fig 4/6 (weak/strong scaling vs processors): the accumulation +
vertex-local HH pipeline on 1/2/4/8 devices. Under ``JAX_PLATFORMS=cpu``
each point runs in a child process with that many virtual CPU devices
(the device count is fixed when JAX starts); on an accelerator every
point runs in this one process over the first 1/2/4/... visible devices,
since a child could not reach a chip the parent holds. The paper's
result: time roughly halves as processors double.

Fig 5 (scaling vs graph size): time vs |E| at fixed resources — the paper's
result: linear in m for both accumulation and estimation.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from benchmarks.common import emit, graph_suite, timer
from repro.core import degreesketch as dsk
from repro.core.hll import HLLConfig
from repro.graph import generators as gen


def _scaling_point(nd: int) -> str:
    """Accumulate + vertex heavy hitters on the first ``nd`` devices."""
    import time

    import jax
    from jax.sharding import Mesh

    from repro.distributed import sketch_dist as sd

    edges = gen.rmat(11, 8, seed=9)
    n = int(edges.max()) + 1
    cfg = HLLConfig(p=8)
    mesh = Mesh(np.asarray(jax.devices()[:nd]), ("data",))
    plan = sd.build_plan(edges, n, nd)

    t0 = time.time()
    regs = sd.dist_accumulate(mesh, "data", plan, cfg)
    jax.block_until_ready(regs)
    acc_t = time.time() - t0

    t0 = time.time()
    tot, vals, ids = sd.dist_triangle_heavy_hitters(
        mesh, "data", plan, cfg, regs, k=10, iters=20, mode="vertex")
    est_t = time.time() - t0
    return f"RESULT,{nd},{acc_t:.3f},{est_t:.3f},{tot:.0f}"


_WORKER = r"""
import sys
from repro.launch import jaxenv
jaxenv.virtual_cpu_devices(int(sys.argv[1]))
from benchmarks.bench_scaling import _scaling_point
print(_scaling_point(int(sys.argv[1])))
"""


def _cpu_child(nd: int) -> tuple[str | None, str]:
    """One scaling point in a child with ``nd`` virtual CPU devices."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _WORKER, str(nd)],
                         capture_output=True, text=True, env=env,
                         timeout=1800, cwd=root)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")]
    err = res.stderr.strip().splitlines()
    return (line[0] if line else None), (err[-1][:120] if err else "no output")


def run(small: bool = True) -> None:
    # Fig 4/6: device scaling
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        counts, point = (1, 2, 4, 8), _cpu_child
    else:
        import jax
        nd_max = jax.device_count()
        counts = tuple(1 << i for i in range(nd_max.bit_length())
                       if 1 << i <= nd_max)
        point = lambda nd: (_scaling_point(nd), "")  # noqa: E731
    for nd in counts:
        line, err = point(nd)
        if line is None:
            emit(f"fig46_scaling/devices={nd}", 0.0, f"ERROR:{err}")
            continue
        _, nd_s, acc_t, est_t, tot = line.split(",")
        emit(f"fig46_scaling/devices={nd}", float(acc_t) * 1e6,
             f"accumulate_s={acc_t};estimate_s={est_t};tri_est={tot}")

    # Fig 5: time vs |E| on fixed resources (single device)
    cfg = HLLConfig(p=8)
    for scale in (8, 9, 10, 11):
        edges = gen.rmat(scale, 8, seed=5)
        n = int(edges.max()) + 1
        (_, acc_s) = timer(dsk.accumulate, edges, n, cfg)
        sketch = dsk.accumulate(edges, n, cfg)
        (_, est_s) = timer(dsk.edge_triangle_estimates, sketch,
                           edges[: min(len(edges), 4096)], block=2048,
                           iters=20)
        emit(f"fig5_edges/m={len(edges)}", acc_s * 1e6,
             f"accumulate_s={acc_s:.3f};tri_per_edge_us="
             f"{est_s/min(len(edges),4096)*1e6:.1f}")


if __name__ == "__main__":
    run()
