"""Chip smoke: drive the sketch service's main path once on a TPU and check it.

    python chip_smoke.py              # one chip: main phase + Pallas phase
    python chip_smoke.py --chips 4    # four chips: sharded engine + failover

One process holds the chip(s) for the whole run; nothing falls back to the
CPU. Exits non-zero, without a result line, when JAX finds no TPU.

Main phase (one chip): a Graph500 Kronecker graph (scale 20, edgefactor
16) is sketched at p=8 by ``engine.open`` with the default impl and
layout. Three quarters of the edges are ingested up front; the rest
streams in through a ``QueryServer`` while client threads query. Then
degree, union, intersection and neighborhood(t_max=3) requests are served
and checked against the plain reference on the same data:

* the register table is bit-identical to ``core.degreesketch.accumulate``;
* degrees are within the HLL error at p of ``graph.exact.degrees``;
* for a sample of vertices, the ``D^1`` rows equal a host max over their
  neighbours' one-key rows, and the ``D^2`` rows of one propagate pass
  equal a host max over their own and their neighbours' ``D^1`` rows;
* served answers are bit-identical to direct engine calls afterwards.

Pallas phase (one chip): the same with ``impl="pallas"`` at the largest
scale the kernels' VMEM bound admits, byte and packed layout, against a
``ref`` engine of the same layout: register and ``D^2`` panels must be
bit-identical, answers equal to the repo's pallas-vs-ref tolerance.

Four chips (``--chips 4``): the sharded engine (4 shards) serves the same
queries on the scale-20 graph under the ``ring``, ``ring_overlap`` and
``allgather`` schedules, and one kill-one-host recovery (4 -> 3 shards)
of ``runtime.coordinator`` runs on a scale-16 graph; both must be
bit-identical to a local engine built in this process on the same data.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch import jaxenv  # noqa: E402  (needs the path above)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import engine  # noqa: E402
from repro.core import degreesketch as dsk  # noqa: E402
from repro.core.hashing import bucket_rho  # noqa: E402
from repro.core.hll import HLLConfig  # noqa: E402
from repro.engine import plans  # noqa: E402
from repro.graph import exact, generators as gen  # noqa: E402
from repro.kernels import ops, packing, registry, tiles  # noqa: E402
from repro.serve import QueryServer  # noqa: E402

#: Graph500 scale of the main phase (edgefactor 16, A/B/C = .57/.19/.19).
MAIN_SCALE = 20
#: Graph500 scale of the failover run: it checkpoints and reloads the
#: table several times, so it runs smaller than the main graph.
FAILOVER_SCALE = 16
EDGE_FACTOR = 16
P = 8
T_MAX = 3
SAMPLE = 64          # vertices whose D^1 / D^2 rows are checked on the host
UNION_SETS = 16      # sets per union request
PAIRS = 64           # pairs per intersection request
#: pallas estimates match ref to this relative tolerance (the same bound
#: tests/test_engine.py holds them to): the kernels sum in another order.
PALLAS_RTOL = 1e-5


def log(msg: str) -> None:
    """Progress line on stdout (the result is the last line)."""
    print(msg, flush=True)


def require_tpu() -> dict:
    """The device JAX reports; exit non-zero unless it is a TPU."""
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports {info}); "
              f"this smoke never runs on another device", file=sys.stderr)
        sys.exit(2)
    return info


def check(cond: bool, what: str) -> None:
    """Fail the run (non-zero exit) unless ``cond``."""
    if not cond:
        raise AssertionError(f"check failed: {what}")
    log(f"  ok: {what}")


def timed(fn, *args, **kw):
    """(result, seconds) with the device work finished."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def graph(scale: int, seed: int) -> tuple[np.ndarray, int]:
    """The Graph500 Kronecker graph at ``scale`` and its vertex count."""
    edges, s = timed(gen.rmat, scale, EDGE_FACTOR, seed)
    n = 1 << scale
    log(f"graph: Graph500 scale {scale} edgefactor {EDGE_FACTOR} seed {seed}:"
        f" n={n} m={len(edges)} undirected edges ({s:.3f}s on host)")
    return edges, n


def queries(edges: np.ndarray, n: int, seed: int) -> dict:
    """One batch of each query kind, drawn from the seed."""
    rng = np.random.default_rng(seed + 1)
    sets = [rng.integers(0, n, size=int(rng.integers(1, 9)))
            for _ in range(UNION_SETS)]
    pairs = edges[rng.integers(0, len(edges), size=PAIRS)]
    sample = rng.choice(n, size=SAMPLE, replace=False)
    return {"sets": sets, "pairs": pairs, "sample": sample}


def answers(target, q: dict, schedule: str = "auto") -> dict:
    """Every query kind once, against an engine or a server."""
    return {
        "degrees": np.asarray(target.degrees()),
        "union": np.asarray(target.union_size(q["sets"])),
        "intersection": np.asarray(target.intersection_size(q["pairs"])),
        "neighborhood": np.asarray(target.neighborhood(T_MAX, schedule)[1]),
    }


def serve(eng, edges: np.ndarray, q: dict, blocks: int = 4) -> dict:
    """Stream the held-back quarter through a server while clients query.

    Returns the answers served at the final epoch, each kind timed.
    """
    head = len(edges) - len(edges) // 4
    tail = edges[head:]
    done = threading.Event()
    errors: list[BaseException] = []
    served = {"requests": 0}

    def client(server, seed):
        rng = np.random.default_rng(seed)
        try:
            while not done.is_set():
                kind = int(rng.integers(4))
                if kind == 0:
                    server.degrees()
                elif kind == 1:
                    server.union_size(q["sets"][: int(rng.integers(1, 9))])
                elif kind == 2:
                    server.intersection_size(q["pairs"][: int(
                        rng.integers(1, PAIRS + 1))])
                else:
                    server.neighborhood(int(rng.integers(1, T_MAX + 1)))
                served["requests"] += 1
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    with QueryServer(eng) as server:
        threads = [threading.Thread(target=client, args=(server, 100 + c))
                   for c in range(3)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        step = -(-len(tail) // blocks)
        for s in range(0, len(tail), step):
            server.ingest(tail[s:s + step])
        jax.block_until_ready(eng.regs)
        ingest_s = time.perf_counter() - t0
        done.set()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        log(f"  served {served['requests']} client requests while "
            f"ingesting the last {len(tail)} edges in {blocks} blocks "
            f"({ingest_s:.3f}s)")
        out = {}
        for kind, call in (
                ("degrees", server.degrees),
                ("union", lambda: server.union_size(q["sets"])),
                ("intersection",
                 lambda: server.intersection_size(q["pairs"])),
                ("neighborhood", lambda: server.neighborhood(T_MAX)[1])):
            first, t_first = timed(call)
            again, t_again = timed(call)
            check(np.array_equal(np.asarray(first), np.asarray(again)),
                  f"{kind}: repeated request answers identically")
            out[kind] = np.asarray(first)
            log(f"  {kind}: first {t_first:.6f}s (compiles), "
                f"again {t_again:.6f}s")
    return out


def ingest_head(eng, edges: np.ndarray) -> float:
    """Ingest the first three quarters directly; returns seconds."""
    head = len(edges) - len(edges) // 4
    t0 = time.perf_counter()
    eng.ingest(edges[:head])
    jax.block_until_ready(eng.regs)
    s = time.perf_counter() - t0
    log(f"  ingest of {head} edges: {s:.3f}s ({head / s:.1f} edges/s)")
    return s


def neighbours(edges: np.ndarray, sample: np.ndarray) -> list[np.ndarray]:
    """Adjacency of each sampled vertex (host, by scanning the edge list)."""
    out = []
    for x in sample:
        out.append(np.concatenate([edges[edges[:, 0] == x, 1],
                                   edges[edges[:, 1] == x, 0]]))
    return out


def host_rows(regs, layout: str) -> np.ndarray:
    """A register panel on the host, one byte per register."""
    rows = np.asarray(regs)
    return np.asarray(packing.unpack_rows(rows)) if layout == "packed" \
        else rows


def propagate(eng, src: np.ndarray, dst: np.ndarray) -> jax.Array:
    """One Algorithm 2 pass over ``eng``'s table through its own plan."""
    routing = (jnp.asarray(a) for a in plans.pad_routing(src, dst))
    return plans.build_propagate_plan(eng.kernels)(eng.regs, *routing)


def check_rows(eng, edges: np.ndarray, q: dict, cfg: HLLConfig) -> np.ndarray:
    """D^1 and D^2 rows of the sample against host maxima; returns D^2."""
    layout = eng.kernels.layout
    d1 = host_rows(eng.regs, layout)
    r = cfg.r
    sat = packing.SATURATION if layout == "packed" else 255
    nbrs = neighbours(edges, q["sample"])
    flat = np.concatenate(nbrs).astype(np.uint32)
    bucket, rho = (np.asarray(a) for a in bucket_rho(jnp.asarray(flat),
                                                     cfg.p, cfg.seed))
    want1 = np.zeros((len(nbrs), r), np.uint8)
    owner = np.repeat(np.arange(len(nbrs)), [len(a) for a in nbrs])
    np.maximum.at(want1, (owner, bucket), np.minimum(rho, sat))
    check(np.array_equal(d1[q["sample"]], want1),
          f"D^1 rows of {SAMPLE} sampled vertices equal a host max over "
          f"their neighbours' one-key rows")
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    d2 = host_rows(propagate(eng, src, dst), layout)
    want2 = np.stack([d1[np.append(a, x)].max(axis=0)
                      for x, a in zip(q["sample"], nbrs)])
    check(np.array_equal(d2[q["sample"]], want2),
          f"D^2 rows of {SAMPLE} sampled vertices equal a host max over "
          f"their own and their neighbours' D^1 rows")
    return d2


def check_degrees(deg: np.ndarray, edges: np.ndarray, n: int,
                  cfg: HLLConfig) -> None:
    """Degree estimates against exact degrees, within the HLL error."""
    truth = exact.degrees(n, edges)
    live = truth > 0
    rel = np.abs(deg[live] - truth[live]) / truth[live]
    sigma = 1.04 / np.sqrt(cfg.r)
    log(f"  degree relative error: mean {rel.mean():.6f}, p99 "
        f"{np.quantile(rel, 0.99):.6f}, max {rel.max():.6f}; "
        f"HLL sigma at p={cfg.p} is {sigma:.6f}")
    check(not deg[~live].any(), "isolated vertices estimate 0")
    check(rel.mean() <= sigma, "mean degree error within one HLL sigma")
    check(np.quantile(rel, 0.99) <= 4 * sigma,
          "99% of degree errors within four HLL sigma")


def same(a: dict, b: dict, what: str) -> None:
    """Every answer kind bit-identical."""
    for kind in a:
        check(np.array_equal(a[kind], b[kind]), f"{kind} {what}")


def close(a: dict, b: dict, rtol: float, what: str) -> None:
    """Every answer kind equal to ``rtol``; reports the largest deviation."""
    for kind in a:
        x, y = np.asarray(a[kind], np.float64), np.asarray(b[kind], np.float64)
        dev = float(np.max(np.abs(x - y) / np.maximum(np.abs(y), 1e-30)))
        n_same = int(np.sum(x == y))
        check(np.allclose(x, y, rtol=rtol, atol=0),
              f"{kind} {what} (max rel deviation {dev:.3e}; {n_same}/"
              f"{x.size} bit-identical)")


def main_phase(scale: int, seed: int) -> None:
    """Default engine at Graph500 ``scale``, served and checked."""
    log(f"== main phase: impl={engine.default_impl()} "
        f"layout={engine.default_layout()}, p={P}")
    edges, n = graph(scale, seed)
    cfg = HLLConfig(p=P)
    q = queries(edges, n, seed)
    eng = engine.open(n, cfg)
    log(f"  kernels: {eng.kernels}")
    log(f"  register table: {eng.n_pad} x {eng.regs.shape[1]} uint8 = "
        f"{eng.regs.nbytes} bytes")
    ingest_head(eng, edges)
    served = serve(eng, edges, q)
    same(served, answers(eng, q), "served == direct engine call")
    ref, s = timed(lambda: dsk.accumulate(edges, n, cfg, n_pad=eng.n_pad,
                                          block=1 << 18).regs)
    log(f"  reference accumulate: {s:.3f}s")
    check(np.array_equal(np.asarray(eng.regs), np.asarray(ref)),
          "registers bit-identical to core.degreesketch.accumulate")
    del ref
    check_degrees(served["degrees"], edges, n, cfg)
    check_rows(eng, edges, q, cfg)


def pallas_scale(layout: str) -> int:
    """Largest Graph500 scale whose p=8 table the VMEM bound admits."""
    w = packing.row_width(1 << P, layout)
    return (tiles.PANEL_VMEM_BYTES // w).bit_length() - 1


def count_custom_calls(eng) -> int:
    """``tpu_custom_call``s in the compiled accumulate of ``eng``."""
    k = eng.kernels
    rows = jnp.zeros((2048,), jnp.int32)
    keys = jnp.zeros((2048,), jnp.uint32)
    fn = jax.jit(lambda r, a, b: ops.accumulate(
        r, a, b, eng.cfg, impl=k.impl, layout=k.layout))
    return fn.lower(eng.regs, rows, keys).compile().as_text().count(
        "tpu_custom_call")


def pallas_phase(layout: str, seed: int, scale: int | None = None) -> None:
    """``impl='pallas'`` against ``ref``, same layout, same data."""
    scale = pallas_scale(layout) if scale is None else scale
    log(f"== pallas phase: layout={layout}, p={P}, scale {scale} (largest "
        f"the {tiles.PANEL_VMEM_BYTES}-byte VMEM panel bound admits)")
    edges, n = graph(scale, seed)
    cfg = HLLConfig(p=P)
    q = queries(edges, n, seed)
    eng = engine.open(n, cfg, impl="pallas", layout=layout)
    log(f"  kernels: {eng.kernels}; interpret mode: "
        f"{registry.interpret_mode()}")
    check(not registry.interpret_mode(), "kernels compiled, not interpreted")
    calls = count_custom_calls(eng)
    log(f"  tpu_custom_call in the lowered accumulate: {calls}")
    check(calls > 0, "the accumulate program holds a Mosaic kernel")
    ingest_head(eng, edges)
    served = serve(eng, edges, q)
    same(served, answers(eng, q), "served == direct engine call")
    ref = engine.open(n, cfg, impl="ref", layout=layout)
    ref.ingest(edges)
    check(np.array_equal(np.asarray(eng.regs), np.asarray(ref.regs)),
          "pallas registers bit-identical to ref")
    close(served, answers(ref, q), PALLAS_RTOL, "pallas == ref")
    check_degrees(served["degrees"], edges, n, cfg)
    d2 = check_rows(eng, edges, q, cfg)
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    d2_ref = host_rows(propagate(ref, src, dst), layout)
    check(np.array_equal(d2, d2_ref), "pallas D^2 panel bit-identical to ref")


def sharded_phase(scale: int, seed: int, shards: int) -> None:
    """Sharded engine under every schedule against a local engine."""
    log(f"== sharded phase: {shards} shards, p={P}")
    edges, n = graph(scale, seed)
    cfg = HLLConfig(p=P)
    q = queries(edges, n, seed)
    local = engine.open(n, cfg)
    local.ingest(edges)
    want = answers(local, q)
    eng = engine.open(n, cfg, backend="sharded", shards=shards)
    mesh_devices = {d.id for d in eng.mesh.devices.flat}
    log(f"  mesh: {eng.mesh.shape} over devices {sorted(mesh_devices)}")
    check(len(mesh_devices) == shards == len(jax.devices()),
          f"the mesh spans all {shards} devices")
    check(len({s.device.id for s in eng.regs.addressable_shards}) == shards,
          "the register table is split over every device")
    ingest_head(eng, edges)
    served = serve(eng, edges, q)
    check(np.array_equal(np.asarray(eng.regs), np.asarray(local.regs)),
          "sharded registers bit-identical to the local engine")
    same(served, want, "served (sharded) == local engine")
    for schedule in ("ring", "ring_overlap", "allgather"):
        got, s = timed(lambda: answers(eng, q, schedule))
        log(f"  schedule {schedule}: all kinds in {s:.3f}s")
        same(got, want, f"under {schedule} == local engine")


def failover_phase(scale: int, seed: int, hosts: int) -> None:
    """One kill-one-host recovery against a local engine, same data."""
    from repro.runtime.coordinator import CoordinatorConfig, coordinator
    from repro.runtime.faults import FaultInjector, KillHost
    from repro.runtime.ft import FTConfig

    log(f"== failover: kill one of {hosts} hosts mid-stream, p={P}")
    edges, n = graph(scale, seed)
    cfg = HLLConfig(p=P)
    q = queries(edges, n, seed)
    local = engine.open(n, cfg)
    local.ingest(edges)
    want = answers(local, q)
    block = -(-len(edges) // 16)
    with tempfile.TemporaryDirectory() as d:
        ft = FTConfig(ckpt_dir=os.path.join(d, "ckpt"), keep=2)
        cc = CoordinatorConfig(hosts=hosts, block=block, ckpt_every=4)
        t0 = time.perf_counter()
        rec, stats = coordinator(
            edges, n, cfg, ft=ft, config=cc, backend="sharded",
            faults=FaultInjector(faults=(KillHost(host=1, at_block=9),)))
        log(f"  supervised ingest with one recovery: "
            f"{time.perf_counter() - t0:.3f}s; recovery "
            f"{stats['last_recovery_ms']:.3f}ms, blocks replayed "
            f"{stats['blocks_replayed']}")
    check(stats["recoveries"] == 1 and rec.shards == hosts - 1,
          f"one recovery, {hosts} -> {rec.shards} shards")
    check(np.array_equal(np.asarray(rec.regs)[:n],
                         np.asarray(local.regs)[:n]),
          "recovered registers bit-identical to the local engine")
    for schedule in ("ring", "ring_overlap", "allgather"):
        same(answers(rec, q, schedule), want,
             f"recovered, under {schedule} == local engine")


def peak_bytes() -> int | None:
    """Peak device memory of device 0, where the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv: list[str] | None = None) -> int:
    """Entry point; see the module docstring."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded and failover phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_tpu()
    log(f"device: {device}")
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} devices", file=sys.stderr)
        return 2
    log(f"compile cache: {jaxenv.use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(MAIN_SCALE, args.seed, shards=4)
        failover_phase(FAILOVER_SCALE, args.seed, hosts=4)
    else:
        main_phase(MAIN_SCALE, args.seed)
        for layout in packing.LAYOUTS:
            pallas_phase(layout, args.seed)
    log(f"peak_bytes_in_use: {peak_bytes()}; "
        f"wall {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
