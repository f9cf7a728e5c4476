"""Sharded SketchEngine on up to 8 devices: streamed ingestion with a
mid-stream checkpoint/resume, ring-scheduled Algorithm 2 and distributed
triangle heavy hitters (Algorithms 4/5), all behind the backend-agnostic
``repro.engine`` API — the engine owns the mesh, axis and routing plan
internally, and each ingested block is scattered to its owner shards
inside one donated shard_map step.

    PYTHONPATH=src python examples/distributed_graph_queries.py

Under ``JAX_PLATFORMS=cpu`` the CPU backend is given 8 virtual devices;
on an accelerator the engine shards over the devices that are visible.
"""
from repro.launch import jaxenv

jaxenv.virtual_cpu_devices(8)

import tempfile
import time

import jax
import numpy as np

from repro import engine
from repro.core.hll import HLLConfig
from repro.graph import exact, generators as gen
from repro.graph.stream import EdgeStream


def main() -> None:
    edges, n_f = gen.kronecker_power("wheel16")   # App. C construction
    n = n_f
    tri_truth = exact.kron_edge_triangles(
        gen.named_factor("wheel16")[0], 16, edges)  # O(m) Kronecker formula
    print(f"kronecker wheel16⊗wheel16: n={n} m={len(edges)} "
          f"T={tri_truth.sum()//3}")

    shards = min(8, jax.device_count())
    # Algorithm 1 as a stream: open an empty sharded engine, ingest in
    # blocks (each routed to owner shards in one shard_map step), snapshot
    # mid-stream, resume from the checkpoint, finish the stream.
    t0 = time.time()
    eng = engine.open(n, HLLConfig(p=10), backend="sharded", shards=shards)
    stream = EdgeStream(edges, block=256)
    blocks = list(stream.all_blocks())
    for blk in blocks[: len(blocks) // 2]:
        eng.ingest(blk)
    with tempfile.TemporaryDirectory() as ckpt:
        eng.save(ckpt)
        eng = engine.load(ckpt)      # restores onto the same mesh
    print(f"mid-stream snapshot at m={eng.m}; resumed onto "
          f"{eng.shards}-shard mesh")
    for blk in blocks[len(blocks) // 2:]:
        eng.ingest(blk)
    jax.block_until_ready(eng.regs)
    print(f"streamed accumulate ({shards} shards): {time.time()-t0:.2f}s")

    # streamed == one-shot build, bit for bit, also when sharded
    batch = engine.build(edges, n, HLLConfig(p=10), backend="sharded",
                         shards=shards)
    same = np.array_equal(np.asarray(eng.regs), np.asarray(batch.regs))
    print(f"streamed registers == one-shot build: {same}")

    # Algorithm 2 with the ring schedule (collective_permute pipeline)
    t0 = time.time()
    local, _ = eng.neighborhood(t_max=3, schedule="ring")
    truth = exact.neighborhood_truth(n, edges, 3)
    print(f"neighborhood t<=3 (ring schedule): {time.time()-t0:.2f}s")
    for t in range(3):
        tv = truth[t].astype(float)
        m = tv > 0
        print(f"  t={t+1}: MRE={np.mean(np.abs(local[t][m]-tv[m])/tv[m]):.3f}")

    # Algorithm 4: distributed edge heavy hitters. Kronecker graphs have
    # heavily TIED triangle counts (paper Fig. 3, the em⊗em discussion:
    # "even a perfect heavy hitter extraction procedure will fail"), so we
    # score against the tied class: any returned edge whose true count
    # reaches the 10th-largest value is a hit.
    tot, vals, ids = eng.triangle_heavy_hitters(k=10, mode="edge")
    thresh = np.sort(tri_truth)[-10]
    tri_lookup = {tuple(e): t for e, t in zip(map(tuple, edges), tri_truth)}
    hits = sum(tri_lookup.get(tuple(e), 0) >= thresh for e in ids)
    print(f"edge HH: global T̃={tot:.0f} (true {tri_truth.sum()//3}), "
          f"top-10 tied-class recall={hits/10:.1f} "
          f"(threshold T={thresh}, {int((tri_truth >= thresh).sum())} edges tie)")

    # persistence: reload the sharded sketch and re-answer a query
    with tempfile.TemporaryDirectory() as ckpt:
        eng.save(ckpt)
        eng2 = engine.load(ckpt)    # restores mesh, plan and registers
        same = np.array_equal(eng2.degrees(), eng.degrees())
        print(f"save -> load (sharded): degree answers bit-identical: {same}")


if __name__ == "__main__":
    main()
