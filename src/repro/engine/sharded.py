"""ShardedEngine: SPMD backend wrapping ``repro.distributed.sketch_dist``.

The engine owns the Mesh, axis name and host-side ``DistPlan`` — callers
never thread ``(mesh, axis, plan, cfg, regs, ...)`` through free functions.
The register table lives sharded over the mesh axis (block vertex
partition f); shared queries (degrees, union, intersection, mixed-kind
batches) run on the global sharded array under jit through the same
fused estimation plans as the local backend (DESIGN.md §10 — the plan
key's backend/shard coordinates keep the compiled programs distinct),
while propagation and heavy hitters use the shard_map schedules
(DESIGN.md §2, §3). Jitted steps — including the shard_map programs
built by ``sketch_dist`` — are cached through the shared query-plan
cache with the shard count in the key (DESIGN.md §3b).

Streaming (DESIGN.md §3a): the vertex partition is fixed at ``open`` time
(``sd.vertex_partition`` is edge-independent), each ``ingest`` block is
routed to owner shards host-side via ``graph.stream.bucket_by_owner`` and
scatter-maxed inside ONE donated shard_map step, and the full ``DistPlan``
(ring/allgather/triangle routings) is rebuilt lazily from the accumulated
edge list only when a propagation or triangle query needs it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sketch_dist as sd
from repro.engine import plans
from repro.engine.base import SketchEngine, bucket
from repro.graph import stream as gstream
from repro.kernels import packing

__all__ = ["ShardedEngine", "build_ingest_step"]

_AXIS = "sketch"


class ShardedEngine(SketchEngine):
    """Mesh-sharded engine: registers uint8[n_pad, r] block-sharded on axis 0."""

    backend = "sharded"

    def __init__(self, regs, n, cfg, edges, impl, *, mesh, shards,
                 plan=None, layout="byte"):
        super().__init__(regs, n, cfg, edges, impl=impl, layout=layout)
        self.mesh = mesh
        self.axis = _AXIS
        self.shards = int(shards)
        self.v_loc = self.n_pad // self.shards
        self._dist_plan = plan

    # ------------------------------------------------------------- plan
    @property
    def plan(self) -> "sd.DistPlan":
        """The routing ``DistPlan`` for the edges ingested so far.

        Rebuilt lazily after ingest/merge invalidates it — the plan is a
        pure function of (edges, n, shards), and its vertex partition
        matches the one fixed at ``open`` time by construction
        (``sd.vertex_partition``). Requires a tracked edge list.

        The lazy build is double-checked under the engine's snapshot lock:
        read-only snapshot views (DESIGN.md §3d) may field triangle /
        neighborhood requests from several reader threads at once, and a
        snapshot taken before the plan existed rebuilds it exactly once.
        A snapshot taken *after* the writer built it shares the plan
        outright (it is immutable and matches the snapshot's edge list).
        """
        if self._dist_plan is None:
            with self._snap_lock:
                if self._dist_plan is None:
                    with plans.span("ds.engine.routing"):
                        edges = self._require_edges(
                            "the distributed routing plan")
                        rs = self._replicas
                        self._dist_plan = sd.build_plan(
                            edges, self.n, self.shards,
                            replica_ids=None if rs is None else rs.ids)
        return self._dist_plan

    def _invalidate_edge_caches(self, appended: bool = False) -> None:
        """Ingest/merge moved the edge list: drop plan + propagate caches."""
        super()._invalidate_edge_caches(appended)
        self._dist_plan = None

    def _on_replicas_changed(self) -> None:
        """A new replica id set reroutes hot-source edges: rebuild the plan.

        Row *refreshes* (same ids, new version) never land here — the
        routing is a pure function of (edges, n, shards, replica ids) and
        the propagate schedules re-gather replica rows per pass anyway.
        """
        self._dist_plan = None

    def _place_replica_rows(self, rows):
        """Replicate the uint8[K_pad, w] replica panel across every shard.

        This is the whole point of the placement policy (DESIGN.md §12):
        hot rows live on *all* shards, so query gathers and propagate
        pre-passes touching them are shard-local.
        """
        return jax.device_put(rows, NamedSharding(self.mesh, P(None, None)))

    def _plan_scope(self) -> tuple:
        """Shard count distinguishes mesh-closed plans in the shared cache."""
        return ("shards", self.shards)

    # ------------------------------------------------------ construction
    @staticmethod
    def _make_mesh(shards: int):
        """A 1-D device mesh over the sketch axis (validates device count)."""
        if shards > jax.device_count():
            raise ValueError(
                f"shards={shards} exceeds visible devices "
                f"({jax.device_count()}); set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=... before "
                f"importing jax, or lower shards")
        # Auto axes: the fused query plans are plain jitted gathers on the
        # sharded panel and leave the collectives to the partitioner.
        auto = (AxisType.Auto,)
        if shards == jax.device_count():
            # make_mesh orders the devices along the physical ICI ring
            return jax.make_mesh((shards,), (_AXIS,), axis_types=auto)
        # a subset (e.g. the survivors of a failover) is no physical TPU
        # slice, which make_mesh refuses: take the first devices in order
        return Mesh(np.asarray(jax.devices()[:shards]), (_AXIS,),
                    axis_types=auto)

    @classmethod
    def open(cls, n: int, cfg, *, shards: int | None = None,
             impl: str = "ref", layout: str = "byte") -> "ShardedEngine":
        """An empty sharded engine over [0, n), ready to ingest.

        Builds the mesh, fixes the block vertex partition (n_pad, v_loc)
        from (n, shards) alone, and places a zeroed register table
        block-sharded over the mesh axis (row width follows ``layout`` —
        r bytes, or r/2 packed). ``shards`` defaults to the visible
        device count.
        """
        shards = shards or jax.device_count()
        mesh = cls._make_mesh(shards)
        n_pad, _ = sd.vertex_partition(n, shards)
        width = packing.row_width(cfg.r, layout)
        # zeroed on the devices: a host table would be the whole
        # deployment's bytes (17.2 GB at scale 24, p=10) copied at open
        zeros = jax.jit(lambda: jnp.zeros((n_pad, width), jnp.uint8),
                        out_shardings=NamedSharding(mesh, P(_AXIS, None)))
        return cls(zeros(), n, cfg, np.zeros((0, 2), np.int32), impl,
                   mesh=mesh, shards=shards, layout=layout)

    @classmethod
    def build(cls, edges: np.ndarray, n: int, cfg, *,
              shards: int | None = None, impl: str = "ref",
              layout: str = "byte") -> "ShardedEngine":
        """Algorithm 1, distributed, in one call: ``open`` + ``ingest``.

        Batch construction is the streaming path (route edges to owner
        shards, donated scatter-max per block), so one-shot and streamed
        accumulation produce bit-identical sharded registers (tested).
        """
        return cls.open(n, cfg, shards=shards, impl=impl,
                        layout=layout).ingest(edges)

    @classmethod
    def from_regs(cls, regs, n: int, cfg, *,
                  edges: np.ndarray | None = None, shards: int | None = None,
                  impl: str = "ref", layout: str = "byte") -> "ShardedEngine":
        """Re-host an unsharded row table uint8[>=n, w] onto a fresh mesh.

        The rows are re-padded to the mesh's vertex partition before
        device_put — so a checkpoint taken at one shard count restores at
        any other, and a mid-stream checkpoint resumes ingestion exactly.
        The routing plan, when needed, is rebuilt from ``edges`` (a pure
        function of the edge list and shard count); engines restored
        without ``edges`` answer register queries only.
        """
        shards = shards or jax.device_count()
        mesh = cls._make_mesh(shards)
        n_pad, _ = sd.vertex_partition(n, shards)
        rows = np.asarray(regs, dtype=np.uint8)[:n]
        width = packing.row_width(cfg.r, layout)
        if rows.shape[1] != width:
            raise ValueError(
                f"register rows have width {rows.shape[1]}, expected "
                f"{width} for r={cfg.r} under layout={layout!r}")
        full = np.zeros((n_pad, rows.shape[1]), np.uint8)
        full[: rows.shape[0]] = rows
        sharded = jax.device_put(full, NamedSharding(mesh, P(_AXIS, None)))
        return cls(sharded, n, cfg, edges, impl, mesh=mesh, shards=shards,
                   layout=layout)

    # ------------------------------------------------------ backend hooks
    def _accumulate_block(self, chunk: np.ndarray) -> None:
        """Route one edge block to owner shards and scatter-max in one step.

        ``bucket_by_owner`` expands the block to both directed orientations
        grouped by owner shard (Algorithm 1's Send context, host-side); the
        per-shard panels are padded to a common power-of-two edge capacity
        (one compile per capacity bucket, cached in the shared plan cache)
        and the register panel is donated through the jitted shard_map, so
        the steady-state ingest loop allocates only the small routed index
        arrays.

        The routing — grouping, per-shard fill and padding, uploads — is
        the span ``ds.engine.ingest.route``; the event counters
        ``route_slots`` and ``route_padded`` add the block's directed
        slots and the padding slots of the ``shards x cap`` panels, so
        ``route_padded / (route_slots + route_padded)`` is the share of
        the scatter spent on padding (owner imbalance included).
        """
        with plans.span("ds.engine.ingest.route"):
            per = gstream.bucket_by_owner(chunk, self.n_pad, self.shards)
            cap = bucket(max(max(len(p) for p in per), 1))
            dst = np.zeros((self.shards, cap), np.int32)
            key = np.zeros((self.shards, cap), np.uint32)
            msk = np.zeros((self.shards, cap), bool)
            for s, p in enumerate(per):
                k = len(p)
                dst[s, :k] = p[:, 0] - s * self.v_loc
                key[s, :k] = p[:, 1].astype(np.uint32)
                msk[s, :k] = True
            sh = NamedSharding(self.mesh, P(_AXIS, None))
            routed = [jax.device_put(a, sh) for a in (dst, key, msk)]
        slots = 2 * len(chunk)
        plans.record_event("route_slots", slots)
        plans.record_event("route_padded", self.shards * cap - slots)
        fn = self._plan("ingest", bucket=(cap,),
                        builder=lambda: build_ingest_step(
                            self.mesh, self.kernels, self.cfg, self.impl))
        self._regs = fn(self._regs, *routed)

    def _place_rows(self, full: np.ndarray) -> jax.Array:
        """Block-shard a full row table over the mesh axis (for merge)."""
        return jax.device_put(full, NamedSharding(self.mesh, P(_AXIS, None)))

    def _propagate(self, regs, schedule):
        if schedule in ("auto", "ring", "ring_overlap"):
            return sd.dist_propagate_ring(self.mesh, self.axis, self.plan,
                                          regs, layout=self.layout,
                                          overlap=(schedule ==
                                                   "ring_overlap"))
        if schedule == "allgather":
            return sd.dist_propagate_allgather(self.mesh, self.axis,
                                               self.plan, regs,
                                               layout=self.layout)
        raise ValueError(
            f"schedule must be 'auto', 'ring', 'ring_overlap' or "
            f"'allgather', got {schedule!r}")

    def triangle_heavy_hitters(self, k, *, mode="edge", iters=30):
        """Algorithms 4/5 over the mesh (see base class for the contract).

        Families without a triangle estimator raise ``UnsupportedQuery``
        before any mesh work.
        """
        self._require_kind("triangle")
        if mode not in ("edge", "vertex"):
            raise ValueError(f"mode must be 'edge' or 'vertex', got {mode!r}")
        return sd.dist_triangle_heavy_hitters(
            self.mesh, self.axis, self.plan, self.cfg, self._regs, k,
            iters=iters, mode=mode, layout=self.layout)

    # -------------------------------------------------------- persistence
    def _save_extra(self):
        """Record the shard count so load() restores the same mesh shape."""
        return {"shards": self.shards}


def build_ingest_step(mesh, kernels, cfg, impl: str):
    """The sharded accumulate step over ``mesh``: one donated program.

    Takes ``(regs, dst, key, mask)``, all block-sharded on the mesh axis:
    ``regs`` the uint8[n_pad, w] table, the others ``[shards, cap]``
    panels of shard-local rows, neighbour ids (hashed inside the
    accumulate) and validity. Each shard scatter-maxes its own panel
    into its own rows; nothing crosses chips. Named
    ``shard_accumulate_donated``, so a profile tells it
    (``jit_shard_accumulate_donated``) from the local step.
    """
    def body(regs_local, dst_local, key, mask):
        return kernels.accumulate(regs_local, dst_local[0], key[0], cfg,
                                  mask=mask[0])

    step = jax.shard_map(
        body, mesh=mesh, in_specs=(P(_AXIS, None),) * 4,
        out_specs=P(_AXIS, None), check_vma=(impl != "pallas"))

    def shard_accumulate_donated(regs, dst, key, mask):
        return step(regs, dst, key, mask)

    return jax.jit(shard_accumulate_donated, donate_argnums=(0,))
