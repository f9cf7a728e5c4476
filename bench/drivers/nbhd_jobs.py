"""Neighbourhood-size jobs (Algorithm 2) run back to back on a growing graph.

Set-up: the Graph500 graph of the configuration is generated on the
device from the seed; its first ``base_fraction`` of edges is ingested
into ``engine.open(...)``; the rest is held out in blocks of
``advance_edges``. One job is run to compile its programs.

Window: each job ingests the next held-out block (the stream advancing,
which also invalidates the engine's panel cache) and then calls
``engine.neighborhood(t_max)``; jobs run back to back until one ends
past the window's close, or the held-out edges run out. ``job_s`` is the
time from the window's start to the end of the last job, divided by the
number of jobs.

Checked after the window, against ``bench/reference.py``: the register
table after the last job (bit-exact), and for the last job and
``check_jobs - 1`` more drawn from the seed, the estimates
``N(x, t)``, ``t = 1..t_max``, of a seeded sample of vertices.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, graph500
from bench import reference as R


def _vertex_sample(rng, edges: np.ndarray, n: int, size: int,
                   hubs: int) -> np.ndarray:
    """The ``hubs`` highest-degree vertices plus uniform non-isolated ones."""
    deg = np.bincount(edges.ravel(), minlength=n)
    top = np.argsort(deg, kind="stable")[-hubs:]
    rest = np.setdiff1d(np.flatnonzero(deg), top)
    pick = rng.choice(rest, size=min(size - len(top), len(rest)),
                      replace=False)
    return np.sort(np.concatenate([top, pick]))


def run(ctx) -> dict:
    """Set up, drive the window, check against the reference."""
    from repro import engine
    from repro.core.hll import HLLConfig
    from repro.engine import plans

    cf, tr = ctx.config, ctx.traffic
    sk = cf["sketch"]
    p, hseed = int(sk["p"]), int(sk["hash_seed"])
    t_max = int(tr["t_max"])
    t = {}
    t0 = time.perf_counter()
    edges = graph500.generate(cf["scale"], cf["edgefactor"], ctx.seed,
                              tuple(cf["initiator"]))
    n = 1 << int(cf["scale"])
    m = len(edges)
    head = int(m * float(tr["base_fraction"]))
    step = int(tr["advance_edges"])
    jobs_max = (m - head) // step
    t["generate_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng = engine.open(n, HLLConfig(p=p, seed=hseed,
                                   estimator=sk["estimator"]),
                      backend=sk["backend"], layout=sk["layout"])
    eng.ingest(edges[:head])
    jax.block_until_ready(eng.regs)
    t["ingest_base_s"] = time.perf_counter() - t0
    rng = np.random.default_rng([ctx.seed, 3])
    sample = _vertex_sample(rng, edges, n, int(tr["check_vertices"]),
                            int(tr["check_hubs"]))

    def job(j):
        with jax.profiler.TraceAnnotation("bench.job"):
            with jax.profiler.TraceAnnotation("bench.advance"):
                eng.ingest(edges[head + j * step: head + (j + 1) * step])
            local, _ = eng.neighborhood(t_max)
        return local[:, sample].copy()

    t0 = time.perf_counter()
    job(0)                                    # compiles the job's programs
    t["warm_job_s"] = time.perf_counter() - t0
    ctx.settle()
    traces0 = plans.trace_counts()
    events0 = plans.event_counts()
    start = time.perf_counter()
    ctx.open_window(start)
    t_end = start + ctx.seconds
    answers = {}
    ends = []
    failed = 0
    j = 1
    trace_from = start + float(tr["trace_offset"])
    tracing, traced_at = None, None        # the open trace and its start
    while j < jobs_max and time.perf_counter() < t_end:
        now = time.perf_counter()
        if ctx.trace and traced_at is None and now >= trace_from:
            tracing, traced_at = ctx.traced(), now
            tracing.__enter__()
        try:
            answers[j] = job(j)
        except Exception:  # noqa: BLE001 — a failed job
            failed += 1
        ends.append(time.perf_counter())
        if tracing is not None and ends[-1] - traced_at >= float(
                tr["trace_seconds"]):
            tracing.__exit__(None, None, None)
            tracing = None
        j += 1
    if tracing is not None:
        tracing.__exit__(None, None, None)
    jobs = len(ends)
    traces1 = plans.trace_counts()
    events1 = plans.event_counts()
    ctx.memory_peak()
    e2e = {"job_s": (ends[-1] - start) / jobs if jobs else None}
    t0 = time.perf_counter()
    checks = _check(ctx, eng, edges, head, step, answers, sample, n, p,
                    hseed, t_max)
    t["check_s"] = time.perf_counter() - t0
    return {
        "e2e": e2e,
        "attempted": jobs,
        "failed": failed,
        "checks": checks,
        "compiles": {k: v - traces0.get(k, 0) for k, v in traces1.items()
                     if v - traces0.get(k, 0)},
        "counts": {"jobs": jobs, "jobs_possible": jobs_max - 1,
                   "propagate_passes": events1.get("propagate_pass", 0)
                   - events0.get("propagate_pass", 0),
                   "edges": m, "base_edges": head},
        "timings": t,
    }


def _check(ctx, eng, edges, head, step, answers, sample, n, p, hseed,
           t_max) -> dict:
    """Reference comparison; see the module docstring."""
    r = 1 << p
    done = sorted(answers)
    if not done:
        return {"reg_mismatch": None, "nbhd_gap": None}
    rng = np.random.default_rng([ctx.seed, 4])
    extra = int(ctx.traffic["check_jobs"]) - 1
    others = done[:-1]
    picked = sorted(set(rng.choice(others, size=min(extra, len(others)),
                                   replace=False).tolist()) | {done[-1]})
    gap = 0.0
    tab = R.add_edges(R.table(n, p), edges[:head], p, hseed)
    have = head
    for j in picked:
        upto = head + (j + 1) * step
        tab = R.add_edges(tab, edges[have:upto], p, hseed)
        have = upto
        ref_panel = tab
        ctl_panel = (jax.numpy.minimum(tab, compare.CONTROL_CAP)
                     if ctx.control else None)
        for t in range(1, t_max + 1):
            if t > 1:
                ref_panel = R.propagate(ref_panel, edges[:upto])
                if ctx.control:
                    ctl_panel = R.propagate(ctl_panel, edges[:upto])
            ref = compare.estimates(np.asarray(ref_panel[sample]), r,
                                    control=False)
            served = (compare.estimates(np.asarray(ctl_panel[sample]), r,
                                        control=True)[0]
                      if ctx.control else answers[j][t - 1])
            gap = max(gap, float(np.max(compare.estimate_gap(served, ref,
                                                             r))))
        del ref_panel, ctl_panel
    prog = eng.regs[:n]
    if ctx.control:
        prog = jax.numpy.minimum(tab, compare.CONTROL_CAP)
    return {"reg_mismatch": int(jax.numpy.sum(prog != tab)),
            "nbhd_gap": gap}
