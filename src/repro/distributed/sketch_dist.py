"""Distributed DegreeSketch: shard_map realizations of Algorithms 1-5.

The paper's YGM async message-passing becomes bulk-synchronous SPMD
(DESIGN.md §2). The vertex partition f is a contiguous block partition over
one mesh axis; the host-side :func:`build_plan` plays Algorithm 1's Send
context (routing edges to owner shards, padding to static shapes), and the
shard_map bodies perform the Receive-context scatter-max plus the REDUCE
collectives.

Two schedules for Algorithm 2's SKETCH messages:

* ``dist_propagate_allgather`` — paper-faithful dataflow: materialize all
  remote sketches (one all_gather delivers the full message volume), then
  local merge. Peak memory O(n * r) per device.
* ``dist_propagate_ring``      — beyond-paper: P-step ring of
  collective_permute; step s applies only the edges whose source vertex is
  in the in-flight register block. Peak memory O(2 n r / P) per device and
  the permute of step s+1 overlaps the scatter-max of step s (the TPU
  analogue of YGM's comm/compute overlap).

Both produce bit-identical register tables (tested).

This module holds the SPMD *primitives* (:func:`build_plan`,
:func:`dist_accumulate`, the propagate schedules,
:func:`dist_triangle_heavy_hitters`); the public query surface that
composes them — and the only entry point callers should use — is
``repro.engine.SketchEngine`` (DESIGN.md §3), which owns the
Mesh/axis/plan and caches jitted query plans.

The jitted shard_map programs here are cached through the shared
query-plan cache (``repro.engine.plans``, DESIGN.md §3b) keyed by the
static routing shapes — repeated propagation steps or triangle queries
over the same plan reuse one compiled program instead of re-jitting a
fresh closure per call.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hll, intersection
from repro.core.hll import HLLConfig
from repro.kernels import ops, packing

__all__ = [
    "DistPlan", "vertex_partition", "build_plan", "dist_accumulate",
    "dist_propagate_allgather", "dist_propagate_ring",
    "dist_triangle_heavy_hitters",
]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclass
class DistPlan:
    """Host-side routing plan: the Send context, precomputed.

    Arrays are stacked over shards on axis 0 so shard_map hands each shard
    its own slice. All shapes are static (padded to per-shard maxima).
    """
    n: int
    n_pad: int
    v_loc: int
    num_shards: int
    # accumulation: directed (dst, neighbor) owned by dst shard
    acc_dst_local: np.ndarray    # int32[S, E_acc]
    acc_key: np.ndarray          # uint32[S, E_acc]
    acc_mask: np.ndarray         # bool[S, E_acc]
    # propagation: directed edges grouped by (owner=dst shard, src block)
    ring_dst_local: np.ndarray   # int32[S, S, E_ring]
    ring_src_local: np.ndarray   # int32[S, S, E_ring]
    ring_mask: np.ndarray        # bool[S, S, E_ring]
    # flattened (for the all_gather variant): src global, dst local
    flat_src: np.ndarray         # int32[S, E_flat]
    flat_dst_local: np.ndarray   # int32[S, E_flat]
    flat_mask: np.ndarray        # bool[S, E_flat]
    # undirected edges partitioned by owner of u (for triangle queries)
    tri_u: np.ndarray            # int32[S, E_tri]
    tri_v: np.ndarray            # int32[S, E_tri]
    tri_mask: np.ndarray         # bool[S, E_tri]
    # hot-vertex replica routing (DESIGN.md §12, None when no replicas):
    # propagate edges whose SOURCE is replicated leave the ring/all_gather
    # groups above and resolve from the replicated panel instead — a
    # shard-local scatter pre-pass, no exchange. ``rep_slot`` indexes into
    # the sorted replica id set; ``rep_gids`` is the padded global id
    # vector the schedules gather the replica panel with (from the
    # *current* D^{t-1} panel, so every pass sees fresh rows).
    rep_ids: np.ndarray | None = None         # int64[K] sorted
    rep_gids: np.ndarray | None = None        # int32[K_pad]
    rep_dst_local: np.ndarray | None = None   # int32[S, E_rep]
    rep_slot: np.ndarray | None = None        # int32[S, E_rep]
    rep_mask: np.ndarray | None = None        # bool[S, E_rep]

    @property
    def has_replicas(self) -> bool:
        """Whether this plan routes any edges through the replica panel."""
        return self.rep_ids is not None and len(self.rep_ids) > 0


def vertex_partition(n: int, num_shards: int,
                     pad_multiple: int = 8) -> tuple[int, int]:
    """The block vertex partition f: returns (n_pad, v_loc).

    Pure function of (n, num_shards) — *not* of the edges — so a streaming
    engine can fix its register layout at ``open`` time and a plan rebuilt
    later from whatever edges arrived lands on the same partition.
    """
    n_pad = _round_up(max(n, num_shards), num_shards * pad_multiple)
    return n_pad, n_pad // num_shards


def _group_by_owner(owner: np.ndarray, num_groups: int,
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Sort-based grouping of row indices by owner group.

    Returns ``(order, group_sorted, within, e_cap)``: ``order`` sorts rows
    stably by owner (original order preserved within a group),
    ``group_sorted`` / ``within`` are each sorted row's (group, slot)
    coordinates in a padded ``[num_groups, e_cap]`` panel, and ``e_cap``
    is the per-group capacity (max group size rounded up to 8).

    One O(rows log rows) sort replaces the per-group boolean-scan loop
    (``[rows[owner == g] for g in range(num_groups)]``), which is
    O(num_groups * rows) — quadratic at a production 256-shard mesh.
    """
    order = np.argsort(owner, kind="stable")
    group_sorted = owner[order]
    counts = np.bincount(group_sorted, minlength=num_groups)
    e_cap = _round_up(max(int(counts.max(initial=0)), 1), 8)
    starts = np.zeros(num_groups, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    within = np.arange(len(owner)) - starts[group_sorted]
    return order, group_sorted, within, e_cap


def build_plan(edges: np.ndarray, n: int, num_shards: int,
               pad_multiple: int = 8,
               replica_ids: np.ndarray | None = None) -> DistPlan:
    """Route edges to owner shards (Algorithm 1 Send context, host-side).

    Every grouping (accumulation, ring, all_gather, triangle) is built by
    the same sort-based scheme (:func:`_group_by_owner`) — O(edges log
    edges) total, shard-count independent; the old per-shard boolean-scan
    loops were O(shards * edges).

    ``replica_ids`` (sorted hot-vertex ids, DESIGN.md §12) reroutes the
    propagate edges whose *source* is replicated: they leave the
    ring/all_gather exchange groups and land in shard-local replica
    groups served from the replicated panel — the plan prefers a local
    replica over the owning shard. Under Zipfian traffic this shrinks
    the per-(shard, block) ring capacity, which is dominated by
    hot-vertex degree. Accumulation and triangle groupings are
    replica-independent (they scatter hash keys / gather full panels).
    """
    n_pad, v_loc = vertex_partition(n, num_shards, pad_multiple)
    directed = np.concatenate([edges, edges[:, ::-1]], axis=0)
    own = directed[:, 0] // v_loc

    # --- accumulation blocks (grouped by owner shard of dst) ---
    order, s_own, within, e_acc = _group_by_owner(own, num_shards)
    d_sorted = directed[order]
    acc_dst = np.zeros((num_shards, e_acc), np.int32)
    acc_key = np.zeros((num_shards, e_acc), np.uint32)
    acc_mask = np.zeros((num_shards, e_acc), bool)
    acc_dst[s_own, within] = d_sorted[:, 0] - s_own.astype(np.int32) * v_loc
    acc_key[s_own, within] = d_sorted[:, 1].astype(np.uint32)
    acc_mask[s_own, within] = True

    # --- replica split: propagate edges whose source is replicated are
    # served from the replicated panel (shard-local pre-pass); only the
    # remainder enters the ring / all_gather exchange groups below ---
    rep_ids = rep_gids = rep_dst = rep_slot = rep_mask = None
    prop, prop_own = directed, own
    if replica_ids is not None and len(replica_ids):
        rep_ids = np.unique(np.asarray(replica_ids, np.int64).ravel())
        pos = np.minimum(np.searchsorted(rep_ids, prop[:, 1]),
                         len(rep_ids) - 1)
        hit = rep_ids[pos] == prop[:, 1]
        rep_edges = prop[hit]
        prop, prop_own = prop[~hit], own[~hit]
        g_order, g_own, g_within, e_rep = _group_by_owner(
            rep_edges[:, 0] // v_loc, num_shards)
        g_sorted = rep_edges[g_order]
        rep_dst = np.zeros((num_shards, e_rep), np.int32)
        rep_slot = np.zeros((num_shards, e_rep), np.int32)
        rep_mask = np.zeros((num_shards, e_rep), bool)
        rep_dst[g_own, g_within] = \
            g_sorted[:, 0] - g_own.astype(np.int32) * v_loc
        rep_slot[g_own, g_within] = \
            np.searchsorted(rep_ids, g_sorted[:, 1]).astype(np.int32)
        rep_mask[g_own, g_within] = True
        rep_gids = np.zeros(_round_up(len(rep_ids), 8), np.int32)
        rep_gids[: len(rep_ids)] = rep_ids

    # --- ring blocks: group by (dst shard, src block) ---
    src_block = prop[:, 1] // v_loc
    key = prop_own.astype(np.int64) * num_shards + src_block
    r_order, key_sorted, r_within, e_ring = _group_by_owner(
        key, num_shards * num_shards)
    ring_dst = np.zeros((num_shards, num_shards, e_ring), np.int32)
    ring_src = np.zeros((num_shards, num_shards, e_ring), np.int32)
    ring_mask = np.zeros((num_shards, num_shards, e_ring), bool)
    s_idx = key_sorted // num_shards
    b_idx = key_sorted % num_shards
    r_sorted = prop[r_order]
    ring_dst[s_idx, b_idx, r_within] = \
        r_sorted[:, 0] - s_idx.astype(np.int32) * v_loc
    ring_src[s_idx, b_idx, r_within] = \
        r_sorted[:, 1] - b_idx.astype(np.int32) * v_loc
    ring_mask[s_idx, b_idx, r_within] = True

    # --- flat (all_gather) blocks: grouped by owner shard of dst, over
    # the same replica-stripped propagate edges as the ring ---
    f_order, f_own, f_within, e_flat = _group_by_owner(prop_own, num_shards)
    f_sorted = prop[f_order]
    flat_src = np.zeros((num_shards, e_flat), np.int32)
    flat_dst = np.zeros((num_shards, e_flat), np.int32)
    flat_mask = np.zeros((num_shards, e_flat), bool)
    flat_dst[f_own, f_within] = f_sorted[:, 0] - f_own.astype(np.int32) * v_loc
    flat_src[f_own, f_within] = f_sorted[:, 1]
    flat_mask[f_own, f_within] = True

    # --- triangle edge partition (undirected, owner of u) ---
    own_u = edges[:, 0] // v_loc
    t_order, t_own, t_within, e_tri = _group_by_owner(own_u, num_shards)
    t_sorted = edges[t_order]
    tri_u = np.zeros((num_shards, e_tri), np.int32)
    tri_v = np.zeros((num_shards, e_tri), np.int32)
    tri_mask = np.zeros((num_shards, e_tri), bool)
    tri_u[t_own, t_within] = t_sorted[:, 0]
    tri_v[t_own, t_within] = t_sorted[:, 1]
    tri_mask[t_own, t_within] = True

    return DistPlan(
        n=n, n_pad=n_pad, v_loc=v_loc, num_shards=num_shards,
        acc_dst_local=acc_dst, acc_key=acc_key, acc_mask=acc_mask,
        ring_dst_local=ring_dst, ring_src_local=ring_src, ring_mask=ring_mask,
        flat_src=flat_src, flat_dst_local=flat_dst, flat_mask=flat_mask,
        tri_u=tri_u, tri_v=tri_v, tri_mask=tri_mask,
        rep_ids=rep_ids, rep_gids=rep_gids, rep_dst_local=rep_dst,
        rep_slot=rep_slot, rep_mask=rep_mask)


def _shard_spec(mesh: Mesh, axis: str, *rest) -> NamedSharding:
    return NamedSharding(mesh, P(axis, *rest))


def _jit_cached(query: str, bucket: tuple, cfg, impl: str, extra: tuple,
                builder):
    """Resolve a jitted shard_map program through the shared plan cache.

    Keyed on the static routing shapes (every DistPlan array shape is a
    pure function of (edges, n, shards)) plus whatever closes over the
    program — meshes over the same devices/axis compare equal, so the
    mesh itself stays out of the key. Imported lazily: ``engine.plans``
    is the cache owner and ``repro.engine`` imports this module.
    """
    from repro.engine import plans
    key = plans.PlanKey(query=query, bucket=bucket, cfg=cfg, impl=impl,
                        backend="sharded", extra=extra)
    return plans.global_cache().get(key, builder)


def dist_accumulate(mesh: Mesh, axis: str, plan: DistPlan, cfg: HLLConfig,
                    impl: str = "ref", layout: str = "byte") -> jax.Array:
    """Algorithm 1, distributed: returns regs uint8[n_pad, w] sharded on axis.

    ``impl`` selects the per-shard insert kernel via ``kernels.ops``
    ("ref" = jnp scatter-max oracle, "pallas" = the TPU kernel);
    ``layout`` picks the register row width (w = r bytes, or r/2 packed).
    """

    v_loc = plan.v_loc  # close over the scalar only — a cached body that
    # captured `plan` would pin its O(edges) routing arrays in the LRU

    def build():
        def body(dst_local, key, mask):
            regs_local = hll.empty_table(v_loc, cfg, layout=layout)
            return ops.accumulate(regs_local, dst_local[0], key[0], cfg,
                                  mask=mask[0], impl=impl, layout=layout)

        # pallas_call has no replication rule; the body is purely per-shard
        # anyway, so the check adds nothing here.
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(axis, None)),
            out_specs=P(axis, None), check_vma=(impl != "pallas")))

    f = _jit_cached(
        "dist_accumulate",
        (plan.n_pad, plan.num_shards, plan.acc_dst_local.shape[1]),
        cfg, impl, (axis, layout), build)
    return f(
        jax.device_put(plan.acc_dst_local, _shard_spec(mesh, axis, None)),
        jax.device_put(plan.acc_key, _shard_spec(mesh, axis, None)),
        jax.device_put(plan.acc_mask, _shard_spec(mesh, axis, None)))


def dist_propagate_allgather(mesh: Mesh, axis: str, plan: DistPlan,
                             regs: jax.Array,
                             layout: str = "byte") -> jax.Array:
    """One Algorithm 2 pass; paper-faithful all_gather dataflow.

    The masked-out fill value 0x00 is empty in *both* layouts (two zero
    nibbles), but the scatter-merge itself must be nibble-wise when
    packed — a byte-wise ``.at[].max`` would compare whole packed bytes.

    Replica-aware plans (DESIGN.md §12) prepend a shard-local pre-pass:
    the K replicated source rows are gathered from the *current* D^{t-1}
    panel (inside the compiled program, so every pass sees fresh rows)
    and scatter-maxed locally; the exchange below then carries only the
    replica-stripped edge groups. Register max is commutative and
    idempotent, so the split is bit-identical to the unsplit dataflow.
    """
    if plan.has_replicas:
        return _propagate_allgather_rep(mesh, axis, plan, regs, layout)

    def build():
        def body(regs_local, src, dst_local, mask):
            full = jax.lax.all_gather(regs_local, axis, tiled=True)
            gathered = jnp.where(mask[0][:, None], full[src[0]],
                                 jnp.uint8(0))
            return packing.scatter_max_rows(regs_local, dst_local[0],
                                            gathered, layout=layout)

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(axis, None),
                      P(axis, None)),
            out_specs=P(axis, None)))

    f = _jit_cached(
        "dist_propagate_allgather",
        (plan.n_pad, plan.num_shards, plan.flat_src.shape[1]),
        None, "ref", (axis, layout), build)
    return f(
        regs,
        jax.device_put(plan.flat_src, _shard_spec(mesh, axis, None)),
        jax.device_put(plan.flat_dst_local, _shard_spec(mesh, axis, None)),
        jax.device_put(plan.flat_mask, _shard_spec(mesh, axis, None)))


def _rep_prepass(regs_local, rep_dst, rep_slot, rep_mask, rep_rows,
                 layout: str) -> jax.Array:
    """Shard-local replica pre-pass: merge replicated source rows into the
    local block (each shard reads the replicated panel, no exchange)."""
    hot = jnp.where(rep_mask[:, None], rep_rows[rep_slot], jnp.uint8(0))
    return packing.scatter_max_rows(regs_local, rep_dst, hot, layout=layout)


def _propagate_allgather_rep(mesh: Mesh, axis: str, plan: DistPlan,
                             regs: jax.Array, layout: str) -> jax.Array:
    """Replica-aware all_gather pass (see :func:`dist_propagate_allgather`)."""

    def build():
        def outer(regs, src, dst_local, mask, rep_dst, rep_slot, rep_mask,
                  rep_gids):
            rep_rows = regs[rep_gids]  # K_pad fresh rows from D^{t-1}

            def body(regs_local, src, dst_local, mask, rep_dst, rep_slot,
                     rep_mask, rep_rows):
                out = _rep_prepass(regs_local, rep_dst[0], rep_slot[0],
                                   rep_mask[0], rep_rows, layout)
                full = jax.lax.all_gather(regs_local, axis, tiled=True)
                gathered = jnp.where(mask[0][:, None], full[src[0]],
                                     jnp.uint8(0))
                return packing.scatter_max_rows(out, dst_local[0],
                                                gathered, layout=layout)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis, None),) * 7 + (P(None, None),),
                out_specs=P(axis, None))(
                regs, src, dst_local, mask, rep_dst, rep_slot, rep_mask,
                rep_rows)

        return jax.jit(outer)

    f = _jit_cached(
        "dist_propagate_allgather_rep",
        (plan.n_pad, plan.num_shards, plan.flat_src.shape[1],
         plan.rep_dst_local.shape[1], plan.rep_gids.shape[0]),
        None, "ref", (axis, layout), build)
    sh = _shard_spec(mesh, axis, None)
    return f(
        regs,
        jax.device_put(plan.flat_src, sh),
        jax.device_put(plan.flat_dst_local, sh),
        jax.device_put(plan.flat_mask, sh),
        jax.device_put(plan.rep_dst_local, sh),
        jax.device_put(plan.rep_slot, sh),
        jax.device_put(plan.rep_mask, sh),
        jnp.asarray(plan.rep_gids))


def _ring_loop(buf0, out0, ring_dst, ring_src, ring_mask, *, axis: str,
               num: int, layout: str, overlap: bool):
    """Shared P-step ring body; plain or double-buffered (overlap) form.

    Both forms scatter-max block ``(i - s) mod P`` at step s, so the
    sequential register-max order — and therefore the result — is
    bit-identical. The plain form permutes ``buf`` *after* consuming it;
    the overlap form keeps two in-flight buffers and issues the permute
    that fetches block s+1 *before* the scatter consuming block s, so
    XLA can run the collective-permute concurrently with the scatter
    (classic latency-hiding decomposition; cf. the redco mesh idiom in
    SNIPPETS.md). Peak memory rises from 2 to 3 register panels/device.
    """
    i = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % num) for j in range(num)]

    def apply_block(s, buf, out):
        b = (i - s) % num  # block id currently held in buf
        dst = jax.lax.dynamic_index_in_dim(ring_dst[0], b, keepdims=False)
        src = jax.lax.dynamic_index_in_dim(ring_src[0], b, keepdims=False)
        msk = jax.lax.dynamic_index_in_dim(ring_mask[0], b, keepdims=False)
        gathered = jnp.where(msk[:, None], buf[src], jnp.uint8(0))
        return packing.scatter_max_rows(out, dst, gathered, layout=layout)

    if not overlap:
        def step(s, carry):
            buf, out = carry
            out = apply_block(s, buf, out)
            buf = jax.lax.ppermute(buf, axis, perm)
            return buf, out

        _, out = jax.lax.fori_loop(0, num, step, (buf0, out0))
        return out

    if num == 1:  # single shard: no neighbor to prefetch from
        return apply_block(0, buf0, out0)

    # Prologue: start fetching block 1's buffer before any compute.
    nxt0 = jax.lax.ppermute(buf0, axis, perm)

    def step(s, carry):
        buf, nxt, out = carry
        # Issue the permute for step s+2's buffer first so it overlaps
        # the scatter below (no data dependence between them).
        new_nxt = jax.lax.ppermute(nxt, axis, perm)
        out = apply_block(s, buf, out)
        return nxt, new_nxt, out

    buf, _, out = jax.lax.fori_loop(0, num - 1, step, (buf0, nxt0, out0))
    # Epilogue: the last block needs no trailing permute.
    return apply_block(num - 1, buf, out)


def dist_propagate_ring(mesh: Mesh, axis: str, plan: DistPlan,
                        regs: jax.Array, layout: str = "byte",
                        overlap: bool = False) -> jax.Array:
    """One Algorithm 2 pass; ring schedule (beyond-paper optimization).

    Step s: shard i holds register block (i - s) mod P in ``buf`` and
    scatter-maxes the edges whose source lies in that block; the next
    permute overlaps the current scatter. Peak memory O(2 n r / P)/device.
    ``overlap=True`` selects the explicitly double-buffered schedule
    (engine ``schedule="ring_overlap"``): the permute fetching the next
    block is issued *before* the scatter consuming the current one, at
    the cost of a third in-flight buffer — see :func:`_ring_loop`. Both
    forms are bit-identical (same sequential scatter-max order) and are
    cached under distinct plan keys.

    Replica-aware plans (DESIGN.md §12) seed the output with a shard-local
    pre-pass over the replicated source rows (gathered fresh from D^{t-1}
    inside the program) before the ring turns; the ring capacity E_ring
    then covers only the replica-stripped edges — under Zipfian hot-vertex
    skew, the bulk of the per-(shard, block) maximum. Bit-identical to the
    replica-free schedule (register max commutes).
    """
    if plan.has_replicas:
        return _propagate_ring_rep(mesh, axis, plan, regs, layout,
                                   overlap=overlap)
    num = plan.num_shards

    def build():
        def body(regs_local, ring_dst, ring_src, ring_mask):
            return _ring_loop(regs_local, regs_local, ring_dst, ring_src,
                              ring_mask, axis=axis, num=num, layout=layout,
                              overlap=overlap)

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None, None),
                      P(axis, None, None), P(axis, None, None)),
            out_specs=P(axis, None)))

    f = _jit_cached(
        "dist_propagate_ring_overlap" if overlap else "dist_propagate_ring",
        (plan.n_pad, plan.num_shards, plan.ring_dst_local.shape[2]),
        None, "ref", (axis, layout), build)
    return f(
        regs,
        jax.device_put(plan.ring_dst_local, _shard_spec(mesh, axis, None, None)),
        jax.device_put(plan.ring_src_local, _shard_spec(mesh, axis, None, None)),
        jax.device_put(plan.ring_mask, _shard_spec(mesh, axis, None, None)))


def _propagate_ring_rep(mesh: Mesh, axis: str, plan: DistPlan,
                        regs: jax.Array, layout: str,
                        overlap: bool = False) -> jax.Array:
    """Replica-aware ring pass (see :func:`dist_propagate_ring`)."""
    num = plan.num_shards

    def build():
        def outer(regs, ring_dst, ring_src, ring_mask, rep_dst, rep_slot,
                  rep_mask, rep_gids):
            rep_rows = regs[rep_gids]  # K_pad fresh rows from D^{t-1}

            def body(regs_local, ring_dst, ring_src, ring_mask, rep_dst,
                     rep_slot, rep_mask, rep_rows):
                out0 = _rep_prepass(regs_local, rep_dst[0], rep_slot[0],
                                    rep_mask[0], rep_rows, layout)
                return _ring_loop(regs_local, out0, ring_dst, ring_src,
                                  ring_mask, axis=axis, num=num,
                                  layout=layout, overlap=overlap)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis, None), P(axis, None, None),
                          P(axis, None, None), P(axis, None, None),
                          P(axis, None), P(axis, None), P(axis, None),
                          P(None, None)),
                out_specs=P(axis, None))(
                regs, ring_dst, ring_src, ring_mask, rep_dst, rep_slot,
                rep_mask, rep_rows)

        return jax.jit(outer)

    f = _jit_cached(
        ("dist_propagate_ring_overlap_rep" if overlap
         else "dist_propagate_ring_rep"),
        (plan.n_pad, plan.num_shards, plan.ring_dst_local.shape[2],
         plan.rep_dst_local.shape[1], plan.rep_gids.shape[0]),
        None, "ref", (axis, layout), build)
    sh1 = _shard_spec(mesh, axis, None)
    sh2 = _shard_spec(mesh, axis, None, None)
    return f(
        regs,
        jax.device_put(plan.ring_dst_local, sh2),
        jax.device_put(plan.ring_src_local, sh2),
        jax.device_put(plan.ring_mask, sh2),
        jax.device_put(plan.rep_dst_local, sh1),
        jax.device_put(plan.rep_slot, sh1),
        jax.device_put(plan.rep_mask, sh1),
        jnp.asarray(plan.rep_gids))


def dist_triangle_heavy_hitters(mesh: Mesh, axis: str, plan: DistPlan,
                                cfg: HLLConfig, regs: jax.Array, k: int,
                                iters: int = 30, mode: str = "edge",
                                layout: str = "byte",
                                ) -> tuple[float, np.ndarray, np.ndarray]:
    """Algorithms 3-5, distributed. mode='edge' (Alg 4) or 'vertex' (Alg 5).

    Returns (T̃ global, top-k values, top-k ids) where ids are edge pairs
    (mode='edge') or vertex ids (mode='vertex'). This is the engine-facing
    primitive behind ``ShardedEngine.triangle_heavy_hitters``.

    Candidate ids travel through the top-k all_gather as int32 alongside the
    float32 values — packing ids into float32 lanes silently corrupts vertex
    ids above 2^24 (the float32 integer-exactness limit).

    Padded lanes (edge mode: routing slots past a shard's real candidate
    count; vertex mode: register rows >= n) score ``-inf`` in the top-k
    inputs, never ``0`` — a zero-scored padding lane would win whenever
    ``k`` exceeds the real candidate count and surface a fabricated
    ``(0, 0)`` edge or an out-of-universe vertex id. The non-finite
    sentinels are trimmed after the global top-k, so the returned arrays
    hold at most ``min(k, #real candidates)`` entries, all real.
    """

    n, n_pad, v_loc = plan.n, plan.n_pad, plan.v_loc  # scalars only: the
    # cached body must not pin the plan's O(edges) routing arrays in the LRU

    def _body(regs_local, u, v, mask):
        full = jax.lax.all_gather(regs_local, axis, tiled=True)
        a = full[u[0]]
        b = full[v[0]]
        if layout == "packed":  # MLE stats read byte registers
            a = packing.unpack_rows(a)
            b = packing.unpack_rows(b)
        est = intersection.mle_intersection(a, b, cfg, iters)
        est = jnp.where(mask[0], est, 0.0)
        total = jax.lax.psum(jnp.sum(est), axis) / 3.0
        if mode == "edge":
            kk = min(k, est.shape[0])
            cand = jnp.where(mask[0], est, -jnp.inf)  # padding never wins
            vals, idx = jax.lax.top_k(cand, kk)
            ids = jnp.stack([u[0][idx], v[0][idx]], axis=-1)  # int32 (kk, 2)
            allv = jax.lax.all_gather(vals, axis, tiled=True)  # (S*kk,)
            alli = jax.lax.all_gather(ids, axis, tiled=True)   # (S*kk, 2)
            gvals, gidx = jax.lax.top_k(allv, min(k, allv.shape[0]))
            return total, gvals, alli[gidx]
        # vertex mode: EST messages -> scatter-add both endpoints, then
        # reduce_scatter back to owner shards (psum_scatter).
        acc = jnp.zeros((n_pad,), jnp.float32)
        acc = acc.at[u[0]].add(est).at[v[0]].add(est)
        acc_local = jax.lax.psum_scatter(acc, axis, scatter_dimension=0,
                                         tiled=True) / 2.0
        vid = (jnp.arange(acc_local.shape[0], dtype=jnp.int32)
               + jax.lax.axis_index(axis) * v_loc)
        acc_local = jnp.where(vid < n, acc_local, -jnp.inf)  # padded rows
        kk = min(k, acc_local.shape[0])
        vals, idx = jax.lax.top_k(acc_local, kk)
        allv = jax.lax.all_gather(vals, axis, tiled=True)
        alli = jax.lax.all_gather(vid[idx], axis, tiled=True)
        gvals, gidx = jax.lax.top_k(allv, min(k, allv.shape[0]))
        return total, gvals, alli[gidx]

    def build():
        return jax.jit(jax.shard_map(
            _body, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(axis, None),
                      P(axis, None)),
            out_specs=(P(), P(), P()), check_vma=False))

    f = _jit_cached(
        "dist_triangle_heavy_hitters",
        (plan.n, plan.n_pad, plan.num_shards, plan.tri_u.shape[1]),
        cfg, "ref", (axis, k, iters, mode, layout), build)
    total, vals, ids = f(
        regs,
        jax.device_put(plan.tri_u, _shard_spec(mesh, axis, None)),
        jax.device_put(plan.tri_v, _shard_spec(mesh, axis, None)),
        jax.device_put(plan.tri_mask, _shard_spec(mesh, axis, None)))
    vals = np.asarray(vals)
    ids = np.asarray(ids).astype(np.int64)
    keep = np.isfinite(vals)  # trim the -inf padding sentinels
    return float(total), vals[keep], ids[keep]
