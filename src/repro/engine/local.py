"""LocalEngine: single-device backend wrapping the core reference path.

Accumulation and propagation go through the engine's resolved
:class:`~repro.kernels.registry.KernelSet` (capability-checked at open,
selecting the "ref" jnp oracles or "pallas" kernels); ingestion uses the
donated accumulate entry (allocation-free block loop, DESIGN.md §3a);
triangle queries route through the engine's sketch family
(``family.triangle_local``, DESIGN.md §13). Query plans come from the
shared LRU
plan cache (DESIGN.md §3b); degrees/union/intersection (and the
mixed-kind batch) resolve the fused estimation kernels from the same
``KernelSet`` (DESIGN.md §10), so ``impl="pallas"`` serves queries
through the single-pass kernel bodies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import plans
from repro.engine.base import SketchEngine, _Routing, bucket, pad_vertices
from repro.graph import stream as gstream
from repro.kernels import registry

__all__ = ["LocalEngine"]


class LocalEngine(SketchEngine):
    """Single-device engine: register table uint8[n_pad, r] on one device."""

    backend = "local"

    # ------------------------------------------------------ construction
    @classmethod
    def open(cls, n: int, cfg, *, impl: str = "ref",
             layout: str = "byte") -> "LocalEngine":
        """An empty engine over vertex universe [0, n), ready to ingest.

        Allocates the zeroed register table uint8[n_pad, w] (n padded to
        a multiple of 8 for the kernels; w is the layout-dependent row
        width — r bytes, or r/2 packed) through the config's sketch
        family; every subsequent ``ingest`` block folds into that one
        panel via a donated jitted step.
        """
        n_pad = pad_vertices(n, 8)
        regs = registry.family_of(cfg).empty_table(n_pad, cfg, layout=layout)
        return cls(regs, n, cfg, np.zeros((0, 2), np.int32), impl=impl,
                   layout=layout)

    @classmethod
    def build(cls, edges: np.ndarray, n: int, cfg, *,
              impl: str = "ref", layout: str = "byte") -> "LocalEngine":
        """Algorithm 1 in one call: ``open(n, cfg)`` + ``ingest(edges)``.

        Batch construction is a thin wrapper over the streaming path, so
        one-shot and block-streamed accumulation are the same code and
        produce bit-identical registers (tested).
        """
        return cls.open(n, cfg, impl=impl, layout=layout).ingest(edges)

    @classmethod
    def from_regs(cls, regs, n: int, cfg, *,
                  edges: np.ndarray | None = None,
                  impl: str = "ref", layout: str = "byte") -> "LocalEngine":
        """Wrap an existing register table uint8[>=n, w] as a query engine.

        Used by loaders and by workloads that build sketch tables
        directly in ``repro.core`` (edge-free engines answer degrees/
        union/intersection; neighborhood/triangles/distance queries need
        ``edges``, whose ids are validated against [0, n)). Row width
        must match ``layout``
        (``ValueError`` otherwise — a packed panel handed to a byte
        engine would be misread, not caught downstream). The row layout
        matches ``open``'s, so a checkpoint taken mid-stream resumes
        ingestion bit-identically.
        """
        from repro.kernels import packing
        regs = jnp.asarray(regs, dtype=jnp.uint8)
        want = packing.row_width(cfg.r, layout)
        if regs.shape[1] != want:
            raise ValueError(
                f"register rows have width {regs.shape[1]}, but layout "
                f"{layout!r} at p={cfg.p} needs width {want}")
        n_pad = pad_vertices(max(n, regs.shape[0]), 8)
        if regs.shape[0] < n_pad:
            regs = jnp.concatenate(
                [regs, jnp.zeros((n_pad - regs.shape[0], regs.shape[1]),
                                 jnp.uint8)])
        return cls(regs, n, cfg, edges, impl=impl, layout=layout)

    # ------------------------------------------------------ backend hooks
    def _accumulate_block(self, chunk: np.ndarray) -> None:
        """Insert both orientations of an edge block (scatter-max).

        Directed pairs are padded up to a power-of-two shape bucket and
        pushed through the kernel set's donated accumulate — the panel
        buffer is donated each step, and jax's jit cache keys on the
        bucketed block shape, so a long stream reuses a handful of
        compiled programs.
        """
        directed = np.concatenate([chunk, chunk[:, ::-1]], axis=0)
        cap = 2 * self.INGEST_BLOCK
        for s in range(0, len(directed), cap):
            sub = directed[s:s + cap]
            padded, mask = gstream.pad_block(sub, bucket(len(sub)))
            self._regs = self.kernels.accumulate_donated(
                self._regs, jnp.asarray(padded[:, 0]),
                jnp.asarray(padded[:, 1].astype(np.uint32)),
                jnp.asarray(mask), cfg=self.cfg)

    def _place_rows(self, full: np.ndarray) -> jax.Array:
        """Single device: the row table goes up as one dense array."""
        return jnp.asarray(full)

    def _canonical_schedule(self, schedule: str) -> str:
        """Validate like the base class, then collapse onto one cache key.

        The local backend runs a single propagate dataflow whichever
        schedule is named, so ``ring``/``allgather``/``auto`` panel sets
        are the same arrays — caching them under one key means switching
        schedule strings never recomputes panels.
        """
        super()._canonical_schedule(schedule)  # ValueError on unknown
        return "local"

    def _propagate(self, regs, schedule):
        rt = self._routing()
        fn = self._plan("propagate", bucket=(rt.cap,),
                        builder=lambda: plans.
                        build_propagate_plan(self.kernels))
        return fn(regs, rt.src, rt.dst, rt.mask)

    def _routing(self) -> _Routing:
        """The propagate routing over every tracked edge.

        Built or extended under the span ``ds.engine.routing``. Ingest
        keeps the routing, so after one the routing covers a prefix
        of the edge list: while the new edges' slots fit its bucket they
        are appended on the device (``ds.engine.routing.extend``, event
        ``routing_extend``), reading only the new edges; otherwise, or with
        no routing, it is rebuilt from the whole list (``routing_full``).
        Slot order differs from a full build's, the slot multiset does
        not, and register max is order-free: the panels are bit-identical.
        """
        rt, m = self._prop_routing, self.m
        if rt is not None and rt.covered == m:
            return rt
        with plans.span("ds.engine.routing"):
            if rt is not None and rt.filled + 2 * (m - rt.covered) <= rt.cap:
                with plans.span("ds.engine.routing.extend"):
                    rt = self._extend_routing(rt,
                                              self._edges_from(rt.covered))
                plans.record_event("routing_extend")
            else:
                rt = self._build_routing()
                plans.record_event("routing_full")
        self._prop_routing = rt
        return rt

    def _build_routing(self) -> _Routing:
        """Pad and upload both orientations of every tracked edge.

        Also runs the extend plan of the new bucket once, on an all-masked
        slice at ``filled`` (padding rewritten with padding), so a later
        extend in this bucket never compiles.
        """
        e = self._require_edges("neighborhood")  # ds.engine.routing.edges
        with plans.span("ds.engine.routing.pad"):
            src, dst, mask = plans.pad_routing(
                np.concatenate([e[:, 0], e[:, 1]]),
                np.concatenate([e[:, 1], e[:, 0]]))
        with plans.span("ds.engine.routing.upload"):
            rt = _Routing(jnp.asarray(src), jnp.asarray(dst),
                          jnp.asarray(mask), filled=2 * len(e),
                          covered=len(e))
        empty = np.zeros(0, np.int32)
        self._extend_plan(rt.cap)(
            rt.src, rt.dst, rt.mask, np.int32(rt.filled),
            *plans.pad_routing(empty, empty, cap=2 * self.INGEST_BLOCK))
        return rt

    def _extend_routing(self, rt: _Routing, tail: np.ndarray) -> _Routing:
        """Append both orientations of ``tail`` after ``rt.filled``.

        Written in fixed slices of ``2 * INGEST_BLOCK`` slots, so every
        extend in a bucket reuses one compiled plan. Returns a new record;
        ``rt`` and its arrays stay as they were.
        """
        size = 2 * self.INGEST_BLOCK
        fn = self._extend_plan(rt.cap)
        fwd = np.concatenate([tail[:, 0], tail[:, 1]])
        rev = np.concatenate([tail[:, 1], tail[:, 0]])
        src, dst, mask = rt.src, rt.dst, rt.mask
        for s in range(0, len(fwd), size):
            src, dst, mask = fn(
                src, dst, mask, np.int32(rt.filled + s),
                *plans.pad_routing(fwd[s:s + size], rev[s:s + size],
                                   cap=size))
        return _Routing(src, dst, mask, filled=rt.filled + len(fwd),
                        covered=rt.covered + len(tail))

    def _extend_plan(self, cap: int):
        return self._plan("routing_extend",
                          bucket=(cap, 2 * self.INGEST_BLOCK),
                          builder=plans.build_routing_extend_plan)

    def triangle_heavy_hitters(self, k, *, mode="edge", iters=30):
        """Algorithms 4/5 on one device (see base class for the contract).

        Routed through the sketch family (``family.triangle_local``,
        which unpacks a transient byte-layout view of packed panels);
        families without a triangle estimator raise ``UnsupportedQuery``.
        """
        self._require_kind("triangle")
        edges = self._require_edges("triangle_heavy_hitters")
        return self.family.triangle_local(self._regs, self.n, self.cfg,
                                          edges, k, mode, iters, self.layout)
