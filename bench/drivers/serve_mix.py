"""Served queries, with or without an ingest stream, through ``QueryServer``.

Set-up: the Graph500 graph of the configuration is generated on the
device from the seed and its first ``base_fraction`` of edges ingested
into ``engine.open(...)``; every query plan bucket the window can reach
is compiled; a ``QueryServer`` is opened over the engine.

Window (``--seconds``):

* queries: an open-loop Poisson arrival at ``rate`` per second (the
  same gaps for every seed, in the seed's order), issued by ``clients``
  threads. A request is, with probability
  ``union_share``, a ``union_size`` of ``sets`` sets of ``set_size``
  vertices, otherwise an ``intersection_size`` of ``pairs`` pairs, each
  an edge of the ingested graph. Vertices and edges are drawn Zipf
  (``zipf_s``) over a seeded permutation of their ranks.
* ingest (``ingest: true``): one closed-loop client sends the held-out
  edges in blocks of ``ingest_block`` to ``QueryServer.ingest``, cycling
  them when they run out, until the window closes.

Checked after the window, against ``bench/reference.py``: the whole
register table after every block the window ingested (bit-exact), and a
seeded sample of served answers, each against the reference at the
epoch that served it.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from bench import compare, graph500, loadgen
from bench import reference as R

WAIT_S = 60.0


class _Ingest:
    """The closed-loop ingest client: applied blocks and their times."""

    def __init__(self, server, blocks: list, t_end: float):
        self.server, self.blocks, self.t_end = server, blocks, t_end
        self.started = 0
        self.done = 0
        self.log = []                  # (block index, t_issue, t_ack)
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-ingest")

    def _run(self):
        k = 0
        try:
            while time.perf_counter() < self.t_end:
                idx = k % len(self.blocks)
                self.started += 1
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.ingest_block"):
                    self.server.ingest(self.blocks[idx])
                self.log.append((idx, t, time.perf_counter()))
                self.done += 1
                k += 1
        except Exception as e:  # noqa: BLE001 — reported as failed blocks
            self.error = repr(e)


def _schedule(ctx, n: int, base: np.ndarray, rate: float, seconds: float,
              seed: int):
    """Arrival offsets and payloads, all drawn from the seed.

    Every seed gets the same Poisson inter-arrival gaps (drawn once from a
    fixed generator) in its own order, so every seed offers the same
    number of requests and the same bursts, placed differently.
    """
    tr = ctx.traffic
    rng = np.random.default_rng([seed, 1])
    gaps = np.diff(loadgen.poisson_arrivals(np.random.default_rng(0), rate,
                                            seconds), prepend=0.0)
    offsets = np.cumsum(rng.permutation(gaps))
    count = len(offsets)
    is_union = rng.random(count) < float(tr["union_share"])
    vz = loadgen.ZipfSampler(n, tr["zipf_s"])
    vperm = loadgen.affine_permutation(rng, n)
    sets = vperm(vz.sample(rng, (count, tr["sets"], tr["set_size"])))
    ez = loadgen.ZipfSampler(len(base), tr["zipf_s"])
    eperm = loadgen.affine_permutation(rng, len(base))
    pairs = base[eperm(ez.sample(rng, (count, tr["pairs"])))]
    payloads = [("union", sets[i]) if is_union[i]
                else ("intersection", pairs[i]) for i in range(count)]
    return offsets, payloads


def _warm(eng, ctx, n: int, base: np.ndarray, method: str) -> int:
    """Compile every union / intersection / mixed bucket; returns plans run."""
    tr = ctx.traffic
    most = int(tr["warm_requests"])
    ks = [1 << i for i in range(most.bit_length()) if (1 << i) <= most]
    rng = np.random.default_rng(0)
    runs = 0
    for a in ks:
        sets = rng.integers(0, n, (a * tr["sets"], tr["set_size"]))
        eng.union_size(sets)
        pairs = base[rng.integers(0, len(base), a * tr["pairs"])]
        eng.intersection_size(pairs, method=method)
        runs += 2
        for b in ks:
            pairs = base[rng.integers(0, len(base), b * tr["pairs"])]
            eng.query_batch(vertex_sets=sets, pairs=pairs, method=method)
            runs += 1
    return runs


class Setup:
    """The cell after set-up: graph, engine, server, ingest blocks."""

    def __init__(self, ctx):
        from repro import engine
        from repro.core.hll import HLLConfig
        from repro.serve import QueryServer

        cf, tr = ctx.config, ctx.traffic
        sk = cf["sketch"]
        self.p, self.hseed = int(sk["p"]), int(sk["hash_seed"])
        self.method = tr["method"]
        self.timings = {}
        t0 = time.perf_counter()
        edges = graph500.generate(cf["scale"], cf["edgefactor"], ctx.seed,
                                  tuple(cf["initiator"]))
        self.n = 1 << int(cf["scale"])
        self.m = len(edges)
        self.head = int(self.m * float(tr["base_fraction"]))
        self.edges = edges
        self.base, held = edges[:self.head], edges[self.head:]
        self.timings["generate_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.eng = engine.open(
            self.n, HLLConfig(p=self.p, seed=self.hseed,
                              estimator=sk["estimator"]),
            backend=sk["backend"], layout=sk["layout"])
        self.eng.ingest(self.base)
        jax.block_until_ready(self.eng.regs)
        self.timings["ingest_base_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.warmed = _warm(self.eng, ctx, self.n, self.base, self.method)
        self.timings["warm_s"] = time.perf_counter() - t0
        self.blocks = []
        if tr["ingest"]:
            bs = int(tr["ingest_block"])
            self.blocks = [held[s:s + bs] for s in range(0, len(held), bs)]
        self.server = QueryServer(self.eng)
        self.server.union_size(np.zeros((1, 1), np.int64))

    def issue(self, payload):
        """Send one request to the server; returns its answer."""
        kind, arg = payload
        if kind == "union":
            return self.server.union_size(arg)
        return self.server.intersection_size(arg, method=self.method)

    def window(self, ctx, offsets, payloads, start: float, seconds: float):
        """Drive one window from ``start``; returns (outcome, ingest client)."""
        t_end = start + seconds
        ing = _Ingest(self.server, self.blocks, t_end) if self.blocks \
            else None
        if ing:
            ing.thread.start()
        tracer = None
        if ctx.trace:
            tracer = threading.Thread(target=_trace_stretch,
                                      args=(ctx, start), daemon=True)
            tracer.start()
        out = loadgen.run_open_loop(
            self.issue, payloads, offsets, start,
            int(ctx.traffic["clients"]),
            span=lambda: jax.profiler.TraceAnnotation("bench.query"),
            before=(lambda: ing.done) if ing else None,
            after=(lambda: ing.started) if ing else None,
            wait_seconds=WAIT_S)
        if ing:
            ing.thread.join(timeout=WAIT_S)
        if tracer:
            tracer.join(timeout=WAIT_S)
        return out, ing


def acked_edges_per_s(st: Setup, ing, t_end: float, seconds: float):
    """Edges of the blocks acknowledged by ``t_end``, per second."""
    if ing is None:
        return None
    return sum(len(st.blocks[i]) for i, _, t in ing.log
               if t <= t_end) / seconds


def run(ctx) -> dict:
    """Set up, drive the window, check against the reference."""
    from repro.engine import plans

    st = Setup(ctx)
    t = st.timings
    t0 = time.perf_counter()
    offsets, payloads = _schedule(ctx, st.n, st.base,
                                  float(ctx.traffic["rate"]), ctx.seconds,
                                  ctx.seed)
    t["schedule_s"] = time.perf_counter() - t0
    ctx.settle()
    st.server.reset_stats()
    traces0 = plans.trace_counts()
    start = time.perf_counter() + 0.05
    ctx.open_window(start)
    out, ing = st.window(ctx, offsets, payloads, start, ctx.seconds)
    stats = st.server.stats()
    traces1 = plans.trace_counts()
    st.server.close()
    ctx.memory_peak()
    summary = loadgen.latency_summary(out)
    e2e = {"query_p95_ms": summary["p95_ms"]}
    if ing:
        e2e["ingest_edges_per_s"] = acked_edges_per_s(
            st, ing, start + ctx.seconds, ctx.seconds)
    applied = [i for i, _, _ in ing.log] if ing else []
    t0 = time.perf_counter()
    checks = _check(ctx, st, applied, payloads, out)
    t["check_s"] = time.perf_counter() - t0
    kinds = [k for k in ("union", "intersection") if k in stats]
    compiles = {k: v - traces0.get(k, 0) for k, v in traces1.items()
                if v - traces0.get(k, 0)}
    failed = summary["failed"] + (1 if ing and ing.error else 0)
    return {
        "e2e": e2e,
        "attempted": summary["requests"] + (ing.started if ing else 0),
        "failed": failed,
        "checks": checks,
        "server_stats": stats,
        "query_kinds": kinds,
        "compiles": compiles,
        "lateness": {k: summary[k] for k in ("late_p50_ms", "late_p95_ms",
                                             "late_max_ms")},
        "counts": {"requests": summary["requests"],
                   "failed": summary["failed"],
                   "errors": out.errors[:3],
                   "p50_ms": summary["p50_ms"], "p99_ms": summary["p99_ms"],
                   "blocks_applied": len(applied),
                   "ingest_error": ing.error if ing else None,
                   "edges": st.m, "base_edges": st.head,
                   "plans_warmed": st.warmed},
        "timings": t,
    }


def _trace_stretch(ctx, start: float) -> None:
    """Trace ``trace_seconds`` of the window from ``trace_offset`` on."""
    tr = ctx.traffic
    delay = start + float(tr["trace_offset"]) - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    try:
        with ctx.traced():
            time.sleep(float(tr["trace_seconds"]))
    except Exception:  # noqa: BLE001 — the run goes on without a trace
        import traceback
        traceback.print_exc()


def _check(ctx, st, applied, payloads, out) -> dict:
    """Reference comparison; see the module docstring.

    Rows come from :class:`bench.reference.Incidence` over the stream.
    Every sampled answer is compared with the reference at each epoch it
    may have been served at (the ingest blocks acknowledged before it was
    issued, up to those started before it was answered), and the closest
    counts: the server serves each drain at one epoch. Requests are drawn
    from those issued during the first pass over the held-out blocks,
    while every epoch still changes the table. The table is
    compared at the final epoch on a seeded sample of rows plus the
    highest-degree rows and every row a sampled answer read.
    """
    tr = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 2])
    good = np.flatnonzero(out.ok)
    if st.blocks:
        # past one pass over the held-out blocks the table no longer
        # changes, so a stale read shows only in a request issued before
        first = good[out.before[good] < len(st.blocks)]
        good = first if len(first) >= int(tr["check_requests"]) else good
    take = min(len(good), int(tr["check_requests"]))
    sample = np.sort(rng.choice(good, size=take, replace=False))
    r, p = 1 << st.p, st.p
    deg = np.bincount(st.edges.ravel(), minlength=st.n)
    hubs = np.argsort(deg, kind="stable")[-int(tr["check_hubs"]):]
    table_rows = np.unique(np.concatenate([
        rng.choice(st.n, size=min(int(tr["check_rows"]), st.n),
                   replace=False), hubs]))
    asked = [np.asarray(payloads[i][1]).ravel() for i in sample]
    block_of = np.full(len(st.edges), -1, np.int64)
    if st.blocks:
        bs = int(tr["ingest_block"])
        block_of[st.head:] = np.arange(len(st.edges) - st.head) // bs
    inc = R.Incidence(st.edges, block_of,
                      np.concatenate(asked + [table_rows]), st.n, p,
                      st.hseed)
    n_blocks = max(len(st.blocks), 1)
    unions, inters = [], []          # (request, rows)
    for i, verts in zip(sample, asked):
        lo, hi = int(out.before[i]), int(out.after[i])
        for e in (range(lo, lo + 1) if ctx.control else range(lo, hi + 1)):
            kind, arg = payloads[i]
            rows = inc.rows(verts, min(e, n_blocks)).reshape(
                np.shape(arg) + (r,))
            (unions if kind == "union" else inters).append((i, rows))
    gaps: dict = {"union": {}, "intersection": {}}
    if unions:
        rows = np.concatenate([x[1] for x in unions])
        ref = compare.union_answers(rows, r)
        served = (compare.union_answers(rows, r, control=True)[0]
                  if ctx.control else np.concatenate(
                      [out.results[i] for i, _ in unions]))
        gap = compare.estimate_gap(served, ref, r).reshape(len(unions), -1)
        for (i, _), g in zip(unions, gap.max(axis=1)):
            gaps["union"][i] = min(gaps["union"].get(i, np.inf), float(g))
    if inters:
        rows = np.concatenate([x[1] for x in inters])
        ref, union = compare.intersection_answers(rows[:, 0], rows[:, 1], p,
                                                  st.method)
        served = (compare.intersection_answers(
            rows[:, 0], rows[:, 1], p, st.method, control=True)[0]
            if ctx.control else np.concatenate(
                [out.results[i] for i, _ in inters]))
        gap = compare.intersection_gap(served, ref, union).reshape(
            len(inters), -1)
        for (i, _), g in zip(inters, gap.max(axis=1)):
            gaps["intersection"][i] = min(
                gaps["intersection"].get(i, np.inf), float(g))
    want = inc.rows(table_rows, min(len(applied), n_blocks))
    got = (np.minimum(want, compare.CONTROL_CAP) if ctx.control
           else np.asarray(st.eng.regs[jax.numpy.asarray(table_rows)]))
    return {"reg_mismatch": int(np.sum(got != want)),
            "union_gap": max(gaps["union"].values(), default=0.0),
            "inter_gap": max(gaps["intersection"].values(), default=0.0)}
