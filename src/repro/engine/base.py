"""SketchEngine: the persistent, backend-agnostic sketch query surface.

The paper's lifecycle is *accumulate in one streaming pass, then serve
queries* ("DegreeSketch behaves as a persistent query engine", §1). This
module is that surface (DESIGN.md §3): an engine owns an accumulated
register table plus whatever backend machinery built it (nothing for
``LocalEngine``; the Mesh/axis/``DistPlan`` for ``ShardedEngine``).

Accumulation is *incremental* (DESIGN.md §3a): ``repro.engine.open``
returns an empty engine, ``ingest(edge_block)`` / ``ingest_stream(stream)``
fold edge blocks into the register panel through a donated jitted
accumulate step (allocation-free hot path, one compile per block shape
bucket), and ``merge(other)`` composes independently accumulated engines
by lane-wise register max — the sketches' closed union operator, which is
what makes them order- and partition-insensitive. Batch construction
(``repro.engine.build``) is a thin wrapper over open + ingest, so streamed
and one-shot accumulation are the same code path and produce bit-identical
registers.

Queries answered through one typed, batched API:

* ``degrees()``                        — d̃(x) for all x (Algorithm 1 output)
* ``union_size(vertex_sets)``          — batched |∪ N(x)| (§6)
* ``intersection_size(pairs)``         — batched |N(x) ∩ N(y)| (Eq. 10)
* ``neighborhood(t_max, schedule=...)``— Algorithm 2, served from the
  t-hop panel cache (DESIGN.md §3c): materialized ``D^t`` panels keyed by
  ``(version, schedule)``, extended incrementally, invalidated by the
  ingest/merge version bump — a repeat on an unchanged engine runs zero
  propagate passes
* ``triangle_heavy_hitters(k, mode=)`` — Algorithms 4/5
* ``query_batch(...)``                 — a mixed degrees/union/intersection
  micro-batch answered by ONE compiled fused program (DESIGN.md §10)
* ``distance_histogram / closeness / effective_diameter`` — HIP-curve
  distance queries (ADS family, DESIGN.md §13), built on the same cached
  D^t panels as ``neighborhood``

The engine is **sketch-family-agnostic** (DESIGN.md §13): the config's
family is resolved once at construction through
``repro.kernels.registry.family_of`` and every family-specific behavior
— estimator tails, pair MLE math, triangle counting, HIP curve math,
config (de)serialization — is reached through that
:class:`~repro.kernels.registry.SketchFamily` object. Query kinds a
family does not serve raise :class:`UnsupportedQuery` up front
(``_require_kind``) instead of producing meaningless numbers.

Query planning lives one layer down (DESIGN.md §3b,
``repro.engine.plans``): inputs are normalized and validated against the
vertex universe, batch dimensions are padded to power-of-two shape
buckets, and the jitted plans are cached in a process-wide LRU keyed by
``(query, bucket, cfg, impl, backend, family)`` — engines with identical
coordinates share compiled programs. Kernel selection goes through the
``repro.kernels.registry``: each engine resolves a capability-checked
:class:`~repro.kernels.registry.KernelSet` once at construction.

Persistence: ``save(path)`` writes the register table + sketch config +
family + plan metadata through ``repro.ckpt.checkpoint`` — legal
mid-stream, since the register panel is a valid sketch of every edge
ingested so far; ``repro.engine.load`` rebuilds an equivalent engine in a
fresh process that can keep ingesting where the saved one stopped
(DESIGN.md §3, §8). Restoring or merging across families raises
``repro.ckpt.checkpoint.FamilyMismatch``.
"""
from __future__ import annotations

import abc
import copy
import operator
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import placement, plans
from repro.kernels import registry

__all__ = ["SketchEngine", "SnapshotFrozen", "UnsupportedQuery", "bucket",
           "pad_vertices", "validate_t_max"]

ENGINE_FORMAT = "degreesketch-engine-v1"

#: Algorithm 2 schedules every backend accepts ("auto" resolves per
#: backend; the local backend runs one dataflow but still validates).
#: "ring_overlap" is the double-buffered ring that issues the permute
#: fetching block s+1 before the scatter consuming block s (DESIGN.md §14).
SCHEDULES = ("auto", "ring", "ring_overlap", "allgather")


class SnapshotFrozen(RuntimeError):
    """Raised when a mutating call (``ingest``/``merge``) hits a snapshot.

    Engines returned by :meth:`SketchEngine.snapshot` are frozen read-only
    views at one version; ingestion goes to the *writer* engine the
    snapshot was taken from (the continuous-serving subsystem in
    ``repro.serve`` owns exactly that split — DESIGN.md §3d).
    """


class UnsupportedQuery(ValueError):
    """Raised for a query kind the engine's sketch family cannot answer.

    Each family declares the query kinds its estimators serve
    (``SketchFamily.query_kinds``, DESIGN.md §13) — e.g. HLL engines
    answer intersections but not distance histograms, ADS engines the
    reverse. The check runs before any input normalization so the caller
    (and the serving frontend, which maps this onto a typed client
    error) fails fast with both the kind and the family named.
    """


def pad_vertices(n: int, multiple: int) -> int:
    """Round ``n`` up to the next multiple (register-table row padding)."""
    return ((n + multiple - 1) // multiple) * multiple


#: Lease release (DESIGN.md §3d): a fresh device buffer with the same
#: contents, dtype and sharding as ``regs`` (elementwise identity, so a
#: sharded input yields an identically sharded output). Run by a writer
#: engine before its next *donating* step when the current panel is
#: leased to a live snapshot — donation would free the buffer under the
#: snapshot's readers.
_clone_panel = jax.jit(lambda regs: regs + jnp.zeros((), regs.dtype))


def validate_t_max(t_max) -> int:
    """Validate a neighborhood horizon: an integer >= 1, returned as int.

    Shared by ``SketchEngine.neighborhood`` and the serving frontend so
    malformed requests fail on the calling thread with the same message
    (``t_max <= 0`` used to return empty arrays silently).
    """
    try:
        t = operator.index(t_max)
    except TypeError:
        raise ValueError(
            f"t_max must be an integer >= 1, got {t_max!r}") from None
    if t < 1:
        raise ValueError(f"t_max must be >= 1, got {t}")
    return t


@dataclass
class _ReplicaSet:
    """Hot-vertex replica panel for one engine version (DESIGN.md §12).

    ``ids`` is the sorted replica vertex set; ``rows`` the gathered
    uint8[K_pad, w] replica panel, placed by the backend (replicated
    across shards on the sharded backend) and byte-identical to the owner
    rows at ``version``. A set whose ``version`` no longer matches the
    engine's is *stale* — queries refresh it lazily (re-gather the K rows)
    before trusting it, so replica-served answers are always bit-identical
    to owner-only execution at the current version.
    """

    ids: np.ndarray
    rows: jax.Array
    version: int


@dataclass
class _PanelSet:
    """Materialized D^t register panels for one (version, schedule) key.

    ``panels[i]`` is D^{i+1}: ``panels[0]`` is the engine's accumulated
    t=1 table itself, each later entry one more Algorithm 2 pass over it
    (DESIGN.md §3c). The set is valid only while the engine's ``version``
    matches ``version`` — ingest/merge donate the register buffer and bump
    the version, so a stale set is dropped, never served.

    ``aux`` holds derived per-hop caches that share the set's lifetime —
    today the ADS family's cumulative HIP curve rows (``aux["hip"][i]``
    is C^{i+1}, host float64[n]); they invalidate with the panels and
    hand off to snapshots the same way (DESIGN.md §13).
    """

    version: int
    schedule: str
    panels: list = field(default_factory=list)
    aux: dict = field(default_factory=dict)


@dataclass(frozen=True)
class _Routing:
    """The local propagate routing: directed edge slots on the device.

    ``src``/``dst``/``mask`` are int32/int32/bool[cap], cap a power-of-two
    shape bucket; slots ``[0, filled)`` hold both orientations of the
    first ``covered`` tracked undirected edges, the rest are the masked
    ``(0, 0)`` padding. Append-only ingest keeps the routing and the next
    propagate appends the newer edges' slots after ``filled`` (a new
    record: snapshots share the old one by reference).
    """

    src: jax.Array
    dst: jax.Array
    mask: jax.Array
    filled: int
    covered: int

    @property
    def cap(self) -> int:
        """Directed slots the routing holds (its shape bucket)."""
        return int(self.src.shape[0])


# Normalization/bucketing moved to repro.engine.plans (DESIGN.md §3b);
# re-exported here for callers that imported them from the engine core.
bucket = plans.bucket
_normalize_sets = plans.normalize_sets
_normalize_pairs = plans.normalize_pairs


class SketchEngine(abc.ABC):
    """Backend-agnostic persistent query engine over an accumulated sketch.

    Construct via :func:`repro.engine.open` (empty, then :meth:`ingest`),
    :func:`repro.engine.build` (open + one ingest) or
    :func:`repro.engine.load`; subclasses only provide the block
    accumulation step, row placement, one propagate step, and the
    distributed heavy-hitter path — every other query is shared here and
    runs identically (bit-for-bit on the same register table) on both
    backends.
    """

    backend = "abstract"

    #: edges per internal accumulate step; ``ingest`` splits larger blocks
    #: so device memory and the compile cache stay bounded regardless of
    #: how callers chunk the stream.
    INGEST_BLOCK = 1 << 15

    #: memory bound of the t-hop panel cache (DESIGN.md §3c): at most this
    #: many materialized D^t panels are retained (~MAX_CACHED_PANELS *
    #: n_pad * r bytes). ``neighborhood(t_max)`` beyond the bound computes
    #: the deeper panels transiently without caching them.
    MAX_CACHED_PANELS = 8

    def __init__(self, regs: jax.Array, n: int, cfg,
                 edges: np.ndarray | None, impl: str = "ref",
                 plan_cache: plans.PlanCache | None = None,
                 layout: str = "byte"):
        # capability check, once — includes the layout keyword every op
        # must accept (DESIGN.md §11), the family coordinate resolved
        # from the config's type (DESIGN.md §13) and the impl's panel bound
        self.kernels = registry.resolve(impl, cfg, layout=layout,
                                        rows=regs.shape[0])
        self.family = registry.family(self.kernels.family)
        self._regs = regs
        self.n = int(n)
        self.cfg = cfg
        self.impl = impl
        self.layout = layout
        if edges is not None:
            raw = np.asarray(edges)
            plans.require_integer_ids(raw, "edges")
            if len(raw):  # range-check before the int32 cast (no wrapping)
                lo, hi = int(raw.min()), int(raw.max())
                if lo < 0 or hi >= self.n:
                    raise ValueError(
                        f"edges contain vertex ids [{lo}, {hi}] outside the "
                        f"engine's universe [0, {self.n})")
            edges = np.ascontiguousarray(raw, dtype=np.int32)
        self._edges0 = edges
        self._edge_chunks: list[np.ndarray] = []
        self._plan_cache = plan_cache or plans.global_cache()
        self._version = 0
        self._prop_routing: _Routing | None = None
        self._panel_set: _PanelSet | None = None
        self._replicas: _ReplicaSet | None = None
        self._frozen = False        # True only on snapshot() views
        self._regs_leased = False   # current panel shared with a snapshot
        self._snap_lock = threading.RLock()  # guards lazy caches on readers

    # ------------------------------------------------------------- state
    @property
    def n_pad(self) -> int:
        """Padded vertex-row count of the register table (>= n)."""
        return int(self._regs.shape[0])

    @property
    def version(self) -> int:
        """Panel version: bumps whenever ingest/merge donates the buffer.

        The enforceable form of the :attr:`regs` staleness warning — a
        handle taken at version v is stale (and, on donating platforms,
        invalid) once ``version != v``. Readers that must never observe a
        donated-away panel (e.g. ``repro.serve.QueryServer``) compare
        versions instead of trusting held references.
        """
        return self._version

    @property
    def frozen(self) -> bool:
        """True iff this engine is a read-only :meth:`snapshot` view.

        Frozen engines answer every query (bit-identically to the writer
        at the snapshot's :attr:`version`) but reject ``ingest``/``merge``
        with :class:`SnapshotFrozen`.
        """
        return self._frozen

    @property
    def regs_leased(self) -> bool:
        """True while the current register panel is shared with a snapshot.

        Set by :meth:`snapshot`; the next donating step (ingest/merge)
        clones the panel first (one copy per rotation, on the writer path)
        so the snapshot's readers never observe a donated-away buffer,
        then donation resumes until the next snapshot.
        """
        return self._regs_leased

    @property
    def regs(self) -> jax.Array:
        """The accumulated register table uint8[n_pad, r] (read-only).

        Each access returns the *current* panel handle. Do not hold it
        across :meth:`ingest`/:meth:`merge` calls — the ingestion step
        donates the panel buffer to XLA, which invalidates previously
        returned arrays; :attr:`version` bumps on every such donation so
        staleness is checkable (``v = eng.version; r = eng.regs; ...;
        assert eng.version == v``).
        """
        return self._regs

    @property
    def plan_cache(self) -> plans.PlanCache:
        """The (shared, LRU-bounded) query-plan cache this engine uses."""
        return self._plan_cache

    @property
    def edges(self) -> np.ndarray | None:
        """Every undirected edge ingested so far, int32[m, 2].

        ``None`` iff the engine was created from a bare register table
        (``from_regs`` without ``edges=``) — such engines answer register
        queries but not edge-replay queries, and never start tracking
        edges even if further blocks are ingested (their panel already
        holds contributions from unknown edges). Chunks appended by
        :meth:`ingest` are consolidated lazily on first access (the span
        ``ds.engine.routing.edges``).
        """
        if self._edges0 is None:
            return None
        if self._edge_chunks:
            with plans.span("ds.engine.routing.edges"):
                self._edges0 = np.concatenate([self._edges0]
                                              + self._edge_chunks)
            self._edge_chunks = []
        return self._edges0

    @property
    def m(self) -> int:
        """Number of undirected edges ingested so far (0 if untracked).

        Counts the chunks in place: nothing is consolidated.
        """
        if self._edges0 is None:
            return 0
        return len(self._edges0) + sum(len(c) for c in self._edge_chunks)

    def _edges_from(self, start: int) -> np.ndarray:
        """Tracked undirected edges ``[start, m)``, int32[m - start, 2].

        Read from the chunks in place, so appending a short tail to the
        propagate routing never consolidates the whole edge list.
        """
        parts, end = [], 0
        for part in (self._edges0, *self._edge_chunks):
            end += len(part)
            if end > start:
                parts.append(part[max(start - end + len(part), 0):])
        return (np.concatenate(parts) if parts
                else np.zeros((0, 2), np.int32))

    def _check_edges(self, query: str) -> None:
        """Raise ValueError unless edges are tracked (consolidates nothing)."""
        if self._edges0 is None:
            raise ValueError(
                f"{query} re-reads the edge stream, but this engine was "
                f"built without edges (from_regs without edges=...)")

    def _require_edges(self, query: str) -> np.ndarray:
        self._check_edges(query)
        return self.edges

    # ---------------------------------------------------------- ingestion
    def ingest(self, edge_block) -> "SketchEngine":
        """Fold a block of undirected edges into the sketch (Algorithm 1).

        Args:
          edge_block: int[k, 2] array-like of vertex pairs, any k >= 0.
            Both orientations of every edge are inserted (vertex u's
            sketch receives neighbor v and vice versa). Vertex ids must
            lie in [0, n) — the vertex universe is fixed at ``open`` time;
            out-of-range ids raise ``ValueError`` before any mutation.

        Blocks larger than ``INGEST_BLOCK`` are split internally; ragged
        tails are padded up to a power-of-two shape bucket, so an
        arbitrary blocking of the stream triggers only O(log block) jit
        compiles, each running with a donated register panel
        (allocation-free hot path). Register max is commutative and
        idempotent, so any blocking/ordering of the same edge multiset
        yields a bit-identical panel to one-shot ``build``.

        Donation bumps :attr:`version`: ``regs`` handles taken before the
        call are stale after it.

        Returns self (engines mutate in place), so calls chain. Raises
        :class:`SnapshotFrozen` on a read-only :meth:`snapshot` view.

        The host part — validation, the int32 cast, block preparation,
        uploads and the asynchronous dispatch — is the span
        ``ds.engine.ingest``; the device work runs after it returns.
        """
        self._check_mutable("ingest")
        with plans.span("ds.engine.ingest"):
            raw = np.asarray(edge_block)
            if raw.ndim != 2 or raw.shape[1] != 2:
                raise ValueError(
                    f"edge_block must have shape (k, 2), got {raw.shape}")
            if raw.shape[0] == 0:
                return self
            plans.require_integer_ids(raw, "edge_block vertex ids")
            lo, hi = int(raw.min()), int(raw.max())  # before the int32 cast:
            if lo < 0 or hi >= self.n:           # ids >= 2^31 must not wrap
                raise ValueError(
                    f"edge block contains vertex ids [{lo}, {hi}] outside "
                    f"the engine's universe [0, {self.n}) fixed at open() "
                    f"time")
            block = np.ascontiguousarray(raw, dtype=np.int32)
            self._release_lease()  # never donate a panel a snapshot reads
            for s in range(0, len(block), self.INGEST_BLOCK):
                self._accumulate_block(block[s:s + self.INGEST_BLOCK])
            self._version += 1
            if self._edges0 is not None:
                self._edge_chunks.append(block)
            self._invalidate_edge_caches(appended=True)
        return self

    def ingest_stream(self, stream) -> "SketchEngine":
        """Drain an :class:`repro.graph.stream.EdgeStream` into the sketch.

        Consumes every substream's blocks in order (``stream.all_blocks``),
        trimming padding — exactly the paper's §2 picture of σ partitioned
        into |P| substreams consumed block-wise with O(block) edge memory.
        Equivalent to ``for blk in stream.all_blocks(): eng.ingest(blk)``.
        """
        for blk in stream.all_blocks():
            self.ingest(blk)
        return self

    def merge(self, other: "SketchEngine") -> "SketchEngine":
        """Fold another engine's sketch into this one (lane-wise max).

        Register max is the sketches' closed union operator (Algorithm 6
        MERGE): merging engines that each ingested a sub-multiset of
        edges is bit-identical to one engine ingesting their union. This
        is what lets independently accumulated engines — different
        processes, round-robin substreams, or a loaded checkpoint plus a
        delta — compose into one.

        Requirements: the same sketch family on both sides
        (:class:`repro.ckpt.checkpoint.FamilyMismatch` otherwise — the
        registers would merge byte-wise but mean different things), then
        an identical config (same p/seed/estimator — sketches merged
        together must share the hash function) and identical vertex count
        ``n`` (``ValueError``). Backends may differ; ``other``'s rows are
        gathered to host and re-placed under this engine's layout. Edge
        tracking: if both engines track edges the lists concatenate; if
        either does not, the merged engine stops tracking (its panel now
        holds unknown contributions).

        Mutates and returns self (donating this engine's panel — bumps
        :attr:`version`); ``other`` is left untouched.
        """
        self._check_mutable("merge")
        if not isinstance(other, SketchEngine):
            raise TypeError(f"can only merge SketchEngine, got {type(other)}")
        if other.family.name != self.family.name:
            from repro.ckpt.checkpoint import FamilyMismatch
            raise FamilyMismatch(
                f"merge: cannot fold a {other.family.name!r}-family engine "
                f"into a {self.family.name!r}-family engine — identical "
                f"register bytes, different estimator semantics")
        if other.cfg != self.cfg:
            raise ValueError(
                f"merge requires an identical sketch config (same hash "
                f"family): {self.cfg} != {other.cfg}")
        if other.n != self.n:
            raise ValueError(
                f"merge requires identical vertex universe: n={self.n} vs "
                f"n={other.n}")
        from repro.kernels import packing
        rows = np.asarray(other.regs, dtype=np.uint8)[: self.n]
        if other.layout != self.layout:
            # byte -> packed saturates (merge-exact); packed -> byte exact
            rows = np.asarray(packing.to_layout(rows, other.layout,
                                                self.layout), np.uint8)
        full = np.zeros((self.n_pad, rows.shape[1]), np.uint8)
        full[: rows.shape[0]] = rows
        fn = self._plan("merge",
                        builder=lambda: plans.build_merge_plan(self.layout))
        self._release_lease()  # the merge plan donates the left panel
        self._regs = fn(self._regs, self._place_rows(full))
        self._version += 1
        mine, theirs = self.edges, other.edges
        if mine is None or theirs is None:
            self._edges0 = None
        else:
            self._edges0 = np.concatenate([mine, theirs])
        self._edge_chunks = []
        self._invalidate_edge_caches()
        return self

    # ---------------------------------------------------------- replication
    @property
    def replicated_ids(self) -> np.ndarray | None:
        """The installed hot-vertex replica set (sorted int64), or ``None``.

        Set by :meth:`replicate` (directly, by a serving placement
        decision, or by ``load`` restoring a checkpoint that carried a
        replica set). The *rows* behind these ids refresh lazily on
        version bumps; the id set only changes through :meth:`replicate`.
        """
        rs = self._replicas
        return None if rs is None else rs.ids.copy()

    def replicate(self, vertex_ids) -> "SketchEngine":
        """Install (or clear) the hot-vertex replica set (DESIGN.md §12).

        The given vertices' register rows are gathered into a small
        read-only replica panel that every query plan can reach without a
        cross-shard fetch: union/intersection/mixed plans concatenate it
        below the register table and remap hot ids onto the replica slots
        host-side (:func:`repro.engine.placement.remap_ids`), and the
        sharded propagate schedules resolve hot-source edges from it
        instead of the ring/all_gather exchange. Replica rows are byte
        copies of the owner rows at the current :attr:`version`; stale
        panels refresh lazily after ingest/merge, so replica-on answers
        stay bit-identical to owner-only execution.

        Args:
          vertex_ids: integer vertex ids in [0, n); duplicates collapse.
            An empty array clears replication. Typically the output of
            :meth:`repro.engine.placement.PlacementPolicy.hot_vertices`
            over serving access stats.

        Returns self (chains like ``ingest``). Raises
        :class:`SnapshotFrozen` on a read-only snapshot view — replicas
        install on the writer and hand off via :meth:`snapshot`.
        """
        self._check_mutable("replicate")
        raw = np.asarray(vertex_ids)
        plans.require_integer_ids(raw, "replicate vertex ids")
        ids = np.unique(raw.astype(np.int64).ravel())
        if len(ids) and (ids[0] < 0 or ids[-1] >= self.n):
            raise ValueError(
                f"replicate got vertex ids [{ids[0]}, {ids[-1]}] outside "
                f"the engine's universe [0, {self.n})")
        with self._snap_lock:
            self._replicas = self._build_replicas(ids) if len(ids) else None
            self._on_replicas_changed()
        return self

    def _build_replicas(self, ids: np.ndarray) -> _ReplicaSet:
        """Gather the replica panel for ``ids`` at the current version."""
        k_pad = plans.bucket(len(ids))
        padded = np.zeros(k_pad, np.int32)
        padded[: len(ids)] = ids
        fn = self._plan("replica_gather", bucket=(k_pad,),
                        builder=plans.build_replica_gather_plan)
        rows = self._place_replica_rows(fn(self._regs, padded))
        return _ReplicaSet(ids=ids, rows=rows, version=self._version)

    def _replicas_current(self) -> _ReplicaSet | None:
        """The replica set, refreshed if the panel version moved on.

        The refresh protocol (DESIGN.md §12): ingest/merge bump
        :attr:`version` without touching the replica set, so the first
        query after a bump re-gathers the K hot rows here (one small
        gather, under the snapshot lock like every lazy reader-side
        mutation). Snapshots inherit a fresh set from :meth:`snapshot`
        and their version never moves, so they skip this path entirely.
        """
        rs = self._replicas
        if rs is None or rs.version == self._version:
            return rs
        with self._snap_lock:
            rs = self._replicas
            if rs is not None and rs.version != self._version:
                rs = self._replicas = self._build_replicas(rs.ids)
            return rs

    def _place_replica_rows(self, rows: jax.Array) -> jax.Array:
        """Backend hook: place the gathered uint8[K_pad, w] replica panel
        (pass-through locally; replicated across the mesh when sharded)."""
        return rows

    def _on_replicas_changed(self) -> None:
        """Backend hook: the replica *id set* changed (install/clear).

        Row refreshes never call this — only routing derived from the id
        set (the sharded backend's ``DistPlan``) needs invalidation.
        """

    # ----------------------------------------------------------- snapshots
    def snapshot(self) -> "SketchEngine":
        """A read-only view of this engine at its current version — O(1).

        The returned engine (same class, same backend) answers every query
        bit-identically to this engine *right now*, and keeps doing so
        while this engine ingests further blocks: register panels are
        immutable arrays, so the snapshot **shares** the current panel
        (pointer swap, never a copy), the consolidated edge list (numpy
        concatenation always allocates fresh arrays, so the handle is
        stable), the resolved kernel set, and the process-wide plan cache
        — compiled programs hand off for free because the plan key
        coordinates ``(cfg, impl, backend, shards)`` are identical.
        Materialized t-hop panels whose version matches hand off too, so
        a served ``neighborhood`` on the snapshot reruns zero propagate
        passes (DESIGN.md §3c → §3d).

        Safety: the current panel is *leased* — the writer's next donating
        ingest/merge clones it first (one copy per rotation, paid on the
        writer path, never by a reader) so no snapshot ever observes a
        donated-away buffer. Multiple snapshots at one version share one
        panel; :class:`SnapshotFrozen` guards the view against mutation.
        """
        edges = self.edges  # consolidate chunks into one stable array
        self._replicas_current()  # refresh replica rows at this version so
        # the view never pays (or races on) a lazy refresh after freezing
        snap = copy.copy(self)
        snap._edges0 = edges
        snap._edge_chunks = []      # never share the writer's chunk list
        snap._frozen = True
        snap._regs_leased = False
        snap._snap_lock = threading.RLock()
        ps = self._panel_set
        if ps is not None and ps.version == self._version:
            # panel-cache handoff: deeper horizons already materialized
            # at this version keep serving from the snapshot (including
            # derived aux rows, e.g. cached HIP curves)
            snap._panel_set = _PanelSet(
                version=ps.version, schedule=ps.schedule,
                panels=list(ps.panels),
                aux={k: list(v) for k, v in ps.aux.items()})
        else:
            snap._panel_set = None
        self._snapshot_fixup(snap)
        self._regs_leased = True
        return snap

    def _snapshot_fixup(self, snap: "SketchEngine") -> None:
        """Backend hook: adjust a freshly shallow-copied snapshot view."""

    def _check_mutable(self, what: str) -> None:
        if self._frozen:
            raise SnapshotFrozen(
                f"{what} on a read-only snapshot (version {self._version}); "
                f"ingest into the writer engine it was taken from")

    def _release_lease(self) -> None:
        """Clone the register panel if a snapshot leases it (pre-donation).

        Called before every donating step; a no-op in the steady state.
        The clone is an elementwise identity under jit, so it preserves
        dtype and device sharding, and costs one panel copy per
        snapshot-then-ingest cycle.
        """
        if self._regs_leased:
            self._regs = _clone_panel(self._regs)
            self._regs_leased = False

    def _invalidate_edge_caches(self, appended: bool = False) -> None:
        """Drop caches derived from the edge list or register panel.

        Called after every ingest/merge: the materialized t-hop panels
        were computed from the pre-donation register table — the panel
        set is keyed by :attr:`version` so a stale set could never be
        *served*, but dropping it here frees its device memory
        immediately. ``appended`` says the edge list only grew at its end
        (ingest): the local propagate routing is then kept, and the next
        propagate appends the new edges to it; any other change (merge)
        drops it.
        """
        if not appended:
            self._prop_routing = None
        self._panel_set = None

    # ----------------------------------------------------- plan caching
    def _plan_scope(self) -> tuple:
        """Backend-specific static plan-key coordinates (e.g. shard count)."""
        return ()

    def _plan(self, query: str, bucket: tuple = (), extra: tuple = (),
              builder=None):
        """Resolve a jitted query plan through the shared LRU plan cache.

        The key is ``(query, bucket, cfg, impl, backend, family,
        scope+extra)`` — engines with identical coordinates share
        compiled programs (DESIGN.md §3b); per-engine state never leaks
        into a plan body.
        """
        key = plans.PlanKey(query=query, bucket=tuple(bucket), cfg=self.cfg,
                            impl=self.impl, backend=self.backend,
                            layout=self.layout,
                            extra=self._plan_scope() + tuple(extra),
                            family=self.kernels.family)
        return self._plan_cache.get(key, builder)

    def _require_kind(self, kind: str) -> None:
        """Gate a query kind on the family's declared query surface."""
        if kind not in self.family.query_kinds:
            raise UnsupportedQuery(
                f"query kind {kind!r} is not served by sketch family "
                f"{self.family.name!r} (supported kinds: "
                f"{', '.join(self.family.query_kinds)})")

    def _resolve_iters(self, iters: int | None) -> int | None:
        """``None`` resolves to the family's iterative-estimator default."""
        return self.family.default_iters if iters is None else iters

    def _estimate_rows(self, regs: jax.Array) -> jax.Array:
        """Per-row cardinality estimates, honoring cfg.estimator and impl.

        Delegates to the engine's resolved :class:`KernelSet`: the fused
        s/z kernel path serves the Flajolet combination; other estimators
        take the fallback recorded (explicitly) at resolve time.
        """
        return self.kernels.estimate_rows(regs, self.cfg)

    # ------------------------------------------------------------ queries
    def degrees(self) -> np.ndarray:
        """d̃(x) for every vertex x < n (the eponymous degree query)."""
        fn = self._plan("degrees", builder=lambda: plans.build_degrees_plan(
            self.cfg, self.kernels))
        out = fn(self._regs)
        with plans.span("ds.engine.query.fetch"):
            return np.asarray(out)[: self.n]

    def union_size(self, vertex_sets):
        """|∪_{x in S} N(x)| for one vertex set or a batch of sets.

        Accepts a 1-D array (returns a float), a list of 1-D arrays
        (ragged batch) or a 2-D array; batches return float arrays [B].
        Vertex ids outside [0, n) raise ``ValueError``; families without
        a union estimator raise :class:`UnsupportedQuery`.
        """
        self._require_kind("union")
        sets, scalar = plans.split_sets(vertex_sets, self.n)
        out = self._union_presplit(sets)
        return float(out[0]) if scalar else out

    def _union_presplit(self, sets: list[np.ndarray]) -> np.ndarray:
        """Batched union over pre-parsed, pre-validated id sets.

        The serving hot path: ``QueryServer`` validates per request on the
        client thread and calls this with the coalesced batch, so the
        single worker thread never re-scans the ids.
        """
        self._require_kind("union")
        rs = self._replicas_current()
        with plans.span("ds.engine.query.pad"):
            ids, mask = plans.pad_sets(sets)
            if rs is not None:
                ids = placement.remap_ids(ids, rs.ids, self.n_pad)
        if rs is not None:
            fn = self._plan(
                "union_rep", bucket=ids.shape + (int(rs.rows.shape[0]),),
                builder=lambda: plans.build_union_plan(
                    self.cfg, self.kernels, replicas=True))
            out = fn(self._regs, rs.rows, ids, mask)
        else:
            fn = self._plan("union", bucket=ids.shape,
                            builder=lambda: plans.build_union_plan(
                                self.cfg, self.kernels))
            out = fn(self._regs, ids, mask)
        with plans.span("ds.engine.query.fetch"):
            return np.asarray(out)[: len(sets)]

    def intersection_size(self, pairs, *, method: str = "mle",
                          iters: int | None = None):
        """|N(x) ∩ N(y)| for one (x, y) pair or a batch (B, 2) of pairs.

        ``method="mle"`` is the paper's Ertl maximum-likelihood estimator
        (the T̃(xy) primitive; ``iters=None`` takes the family's Newton
        solver default); ``method="ie"`` is the inclusion-exclusion
        baseline (Eq. 18, can be negative). Vertex ids outside [0, n)
        raise ``ValueError``; families without a pair estimator raise
        :class:`UnsupportedQuery`.
        """
        self._require_kind("intersection")
        if method not in ("mle", "ie"):
            raise ValueError(f"method must be 'mle' or 'ie', got {method!r}")
        iters = self._resolve_iters(iters)
        arr, scalar = plans.split_pairs(pairs, self.n)
        out = self._intersection_presplit(arr, method, iters)
        return float(out[0]) if scalar else out

    def _intersection_presplit(self, arr: np.ndarray, method: str,
                               iters: int) -> np.ndarray:
        """Batched intersection over pre-parsed, pre-validated (B, 2) pairs.

        Serving hot path counterpart of :meth:`_union_presplit`.
        """
        self._require_kind("intersection")
        rs = self._replicas_current()
        with plans.span("ds.engine.query.pad"):
            ids, mask = plans.pad_pairs(arr)
            if rs is not None:
                ids = placement.remap_ids(ids, rs.ids, self.n_pad)
        if rs is not None:
            fn = self._plan(
                "intersection_rep",
                bucket=(ids.shape[0], int(rs.rows.shape[0])),
                extra=(method, iters),
                builder=lambda: plans.build_intersection_plan(
                    self.cfg, self.kernels, method, iters, replicas=True))
            out = fn(self._regs, rs.rows, ids, mask)
        else:
            fn = self._plan(
                "intersection", bucket=(ids.shape[0],), extra=(method, iters),
                builder=lambda: plans.build_intersection_plan(
                    self.cfg, self.kernels, method, iters))
            out = fn(self._regs, ids, mask)
        with plans.span("ds.engine.query.fetch"):
            return np.asarray(out)[: arr.shape[0]]

    def query_batch(self, *, vertex_sets=None, pairs=None,
                    degrees: bool = False, method: str = "mle",
                    iters: int | None = None) -> dict:
        """Answer a mixed degrees/union/intersection micro-batch at once.

        When two or more kinds are requested, the whole batch runs as ONE
        compiled mixed-kind program (DESIGN.md §10) instead of one program
        per kind — the serving path for coalesced heterogeneous client
        batches. Answers are bit-identical to the per-kind methods (each
        sub-query runs the same fused plan body under the same masks).

        Args:
          vertex_sets: union input (same forms as :meth:`union_size`), or
            ``None`` to skip union queries.
          pairs: intersection input (same forms as
            :meth:`intersection_size`), or ``None`` to skip.
          degrees: include the full d̃(x) table in the answer.
          method / iters: intersection estimator knobs (one group per
            batch; callers with mixed methods split batches;
            ``iters=None`` takes the family's solver default).

        Returns a dict with keys among ``"degrees"`` / ``"union"`` /
        ``"intersection"`` — arrays shaped exactly like the per-kind
        methods' batched returns. Kinds the engine's sketch family does
        not serve raise :class:`UnsupportedQuery`.
        """
        if method not in ("mle", "ie"):
            raise ValueError(f"method must be 'mle' or 'ie', got {method!r}")
        iters = self._resolve_iters(iters)
        if vertex_sets is not None:
            self._require_kind("union")
        if pairs is not None:
            self._require_kind("intersection")
        sets = None
        if vertex_sets is not None:
            sets, _ = plans.split_sets(vertex_sets, self.n)
        arr = None
        if pairs is not None:
            arr, _ = plans.split_pairs(pairs, self.n)
        return self._query_batch_presplit(sets, arr, degrees, method, iters)

    def _query_batch_presplit(self, sets, arr, want_degrees: bool,
                              method: str, iters: int) -> dict:
        """Mixed-kind batch over pre-parsed inputs (serving hot path).

        ``sets`` is a list of validated id arrays or ``None``; ``arr`` a
        validated (B, 2) pair array or ``None``. Single-kind batches fall
        through to the per-kind plans (their buckets are already cached);
        two or more kinds resolve one ``mixed`` plan keyed by the combined
        shape buckets + kinds + estimator coordinates.
        """
        if sets:
            self._require_kind("union")
        if arr is not None and len(arr):
            self._require_kind("intersection")
        kinds = tuple(k for k, want in (
            ("degrees", want_degrees),
            ("union", bool(sets)),
            ("intersection", arr is not None and len(arr) > 0)) if want)
        if len(kinds) < 2:  # nothing to fuse: reuse the per-kind plans
            out = {}
            if want_degrees:
                out["degrees"] = self.degrees()
            if sets:
                out["union"] = self._union_presplit(sets)
            if arr is not None and len(arr):
                out["intersection"] = self._intersection_presplit(
                    arr, method, iters)
            return out
        # dummy panels for absent kinds: the traced body never touches
        # them, but the plan callable takes a fixed argument list
        rs = self._replicas_current()
        with plans.span("ds.engine.query.pad"):
            if sets:
                u_ids, u_mask = plans.pad_sets(sets)
            else:
                u_ids = np.zeros((1, 1), np.int32)
                u_mask = np.zeros((1, 1), bool)
            if arr is not None and len(arr):
                p_ids, p_mask = plans.pad_pairs(arr)
            else:
                p_ids = np.zeros((1, 2), np.int32)
                p_mask = np.zeros((1,), bool)
            if rs is not None:
                u_ids = placement.remap_ids(u_ids, rs.ids, self.n_pad)
                p_ids = placement.remap_ids(p_ids, rs.ids, self.n_pad)
        if rs is not None:
            fn = self._plan(
                "mixed_rep",
                bucket=(u_ids.shape, p_ids.shape[0], int(rs.rows.shape[0])),
                extra=(kinds, method, iters),
                builder=lambda: plans.build_mixed_plan(
                    self.cfg, self.kernels, kinds, method, iters,
                    replicas=True))
            raw = fn(self._regs, rs.rows, u_ids, u_mask, p_ids, p_mask)
        else:
            fn = self._plan(
                "mixed", bucket=(u_ids.shape, p_ids.shape[0]),
                extra=(kinds, method, iters),
                builder=lambda: plans.build_mixed_plan(self.cfg, self.kernels,
                                                       kinds, method, iters))
            raw = fn(self._regs, u_ids, u_mask, p_ids, p_mask)
        out = {}
        with plans.span("ds.engine.query.fetch"):
            if "degrees" in raw:
                out["degrees"] = np.asarray(raw["degrees"])[: self.n]
            if "union" in raw:
                out["union"] = np.asarray(raw["union"])[: len(sets)]
            if "intersection" in raw:
                out["intersection"] = np.asarray(
                    raw["intersection"])[: arr.shape[0]]
        return out

    # ------------------------------------------------- t-hop panel cache
    def _canonical_schedule(self, schedule: str) -> str:
        """Validate ``schedule`` and return the panel-cache key it maps to.

        Raises ``ValueError`` for unknown schedules on *every* backend
        (the local backend used to silently ignore them). Backends that
        run one dataflow regardless collapse all schedules onto one key,
        so semantically identical panel sets are cached once.
        """
        if schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {schedule!r}")
        return "ring" if schedule == "auto" else schedule

    @property
    def panels_cached(self) -> int:
        """Materialized D^t panels currently cached (0 <= · <= t seen).

        Counts the cached set for the engine's *current* version only —
        after ingest/merge this is 0 until the next ``neighborhood`` call
        rematerializes (DESIGN.md §3c).
        """
        ps = self._panel_set
        if ps is None or ps.version != self._version:
            return 0
        return len(ps.panels)

    def _panels_up_to(self, t_max: int, sched: str) -> list:
        """The D^1..D^{t_max} register panels under schedule ``sched``.

        Serves from the cached :class:`_PanelSet` when its
        ``(version, schedule)`` key matches, extending it incrementally:
        ``t_max=5`` after a cached ``t_max=3`` runs exactly passes 4-5.
        On a fully cached horizon zero propagate passes execute (the
        claim ``plans.event_counts()["propagate_pass"]`` asserts). Panels
        beyond :attr:`MAX_CACHED_PANELS` are computed but not retained —
        the cache's memory bound.

        Serialized under the engine's snapshot lock: read-only snapshot
        views may be served by several reader threads at once (DESIGN.md
        §3d), and extending the cached set is the one lazy mutation a
        query performs.
        """
        with self._snap_lock:
            ps = self._panel_set
            if (ps is None or ps.version != self._version
                    or ps.schedule != sched):
                ps = _PanelSet(version=self._version, schedule=sched,
                               panels=[self._regs])
                self._panel_set = ps
            while len(ps.panels) < min(t_max, self.MAX_CACHED_PANELS):
                ps.panels.append(self._propagate_pass(ps.panels[-1], sched))
            out = list(ps.panels[:t_max])
        while len(out) < t_max:  # beyond the memory bound: transient
            out.append(self._propagate_pass(out[-1], sched))
        return out

    def _propagate_pass(self, regs: jax.Array, schedule: str) -> jax.Array:
        """One counted Algorithm 2 pass (the only propagate entry point)."""
        with plans.span("ds.engine.propagate"):
            out = self._propagate(regs, schedule)
        plans.record_event("propagate_pass")
        return out

    def neighborhood(self, t_max: int, schedule: str = "auto",
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Algorithm 2: t-neighborhood sizes for t = 1..t_max.

        Returns (Ñ(x,t) float64[t_max, n], Ñ(t) float64[t_max]). The
        engine's own registers are not mutated — the accumulated t=1 table
        stays queryable afterwards. ``schedule`` selects the distributed
        dataflow ("ring" | "allgather"; "auto" = ring); the local backend
        validates it but runs its single dataflow either way. ``t_max``
        must be an integer >= 1 (``ValueError`` otherwise).

        The D^t panels are materialized through the t-hop panel cache
        (DESIGN.md §3c): repeating the query on an unchanged engine is a
        pure estimate over cached panels (zero propagate passes), a larger
        ``t_max`` extends the cached set incrementally, and ingest/merge
        invalidate it via the :attr:`version` bump.
        """
        t_max = validate_t_max(t_max)
        self._require_kind("neighborhood")
        sched = self._canonical_schedule(schedule)
        self._check_edges("neighborhood")  # the routing rebuild reads them
        est_fn = self._plan("degrees", builder=lambda: plans.
                            build_degrees_plan(self.cfg, self.kernels))
        local = np.zeros((t_max, self.n), dtype=np.float64)
        glob = np.zeros((t_max,), dtype=np.float64)
        for t, regs in enumerate(self._panels_up_to(t_max, sched), start=1):
            with plans.span("ds.engine.estimate.fetch"):
                est = np.asarray(est_fn(regs))[: self.n]
            local[t - 1] = est
            glob[t - 1] = est.sum()
        return local, glob

    # ------------------------------------------- HIP distance queries (§13)
    def _hip_curve(self, t_max: int, sched: str) -> np.ndarray:
        """Cumulative batch-HIP curve C^t float64[t_max, n] (ADS family).

        ``C^t[x]`` estimates |{y : d(x,y) <= t}| from the hop panels:
        C^1 is the plain row estimate of D^1; each later hop adds the
        HIP increments (summed ``2**prev_j`` over registers the hop
        grew — the ``hip_delta`` plan) and floors at the plain estimate
        of D^t, which keeps the curve monotone (histograms stay >= 0)
        and unbiased-per-observed-change (``core.ads`` derivation).

        Curve rows are cached in the t-hop panel set's ``aux["hip"]``
        beside the panels they derive from — repeat distance queries on
        an unchanged engine are pure cache reads, snapshots inherit the
        rows, and ingest/merge invalidate them via the version bump.
        Rows beyond :attr:`MAX_CACHED_PANELS` are computed transiently.
        """
        panels = self._panels_up_to(t_max, sched)
        est_fn = self._plan("degrees", builder=lambda: plans.
                            build_degrees_plan(self.cfg, self.kernels))
        delta_fn = self._plan("hip_delta", builder=lambda: plans.
                              build_hip_delta_plan(self.kernels))
        with self._snap_lock:
            ps = self._panel_set
            cached = []
            if (ps is not None and ps.version == self._version
                    and ps.schedule == sched):
                cached = ps.aux.setdefault("hip", [])
            rows = list(cached[:t_max])
            while len(rows) < t_max:
                i = len(rows)  # 0-based hop index: panels[i] is D^{i+1}
                plain = np.asarray(est_fn(panels[i]),
                                   np.float64)[: self.n]
                if i == 0:
                    cur = plain
                else:
                    delta = np.asarray(delta_fn(panels[i - 1], panels[i]),
                                       np.float64)[: self.n]
                    cur = np.maximum(rows[i - 1] + delta, plain)
                rows.append(cur)
                if len(cached) == i and i < self.MAX_CACHED_PANELS:
                    cached.append(cur)
        return np.stack(rows[:t_max])

    def distance_histogram(self, t_max: int, schedule: str = "auto",
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Per-vertex hop-distance histograms h^t(x) for t = 1..t_max.

        ``h^t(x)`` estimates |{y : d(x,y) = t}| — the per-hop increments
        of the cumulative HIP curve (ADS family only; other families
        raise :class:`UnsupportedQuery`). Returns
        ``(hist float64[t_max, n], glob float64[t_max])`` where ``glob``
        sums each hop's histogram over the vertices. Served from the
        same cached D^t panels as :meth:`neighborhood`, so a repeat on
        an unchanged engine runs zero propagate passes.
        """
        t_max = validate_t_max(t_max)
        self._require_kind("distance_histogram")
        sched = self._canonical_schedule(schedule)
        self._check_edges("distance_histogram")
        curve = self._hip_curve(t_max, sched)
        hist = self.family.hip_histogram(curve)
        return hist, hist.sum(axis=1)

    def closeness(self, t_max: int, schedule: str = "auto") -> np.ndarray:
        """Closeness centralities within a ``t_max``-hop horizon.

        ``c(x) = reach(x) / sum_y d(x, y)`` over the vertices reached
        within ``t_max`` hops, both terms estimated from the HIP curve
        (ADS family only). Returns float64[n]; isolated vertices get 0.
        """
        t_max = validate_t_max(t_max)
        self._require_kind("closeness")
        sched = self._canonical_schedule(schedule)
        self._check_edges("closeness")
        return self.family.hip_closeness(self._hip_curve(t_max, sched))

    def effective_diameter(self, t_max: int, q: float = 0.9,
                           schedule: str = "auto") -> float:
        """Effective diameter: smallest t where a ``q`` fraction of the
        reachable pairs within ``t_max`` hops is covered.

        Linearly interpolated between hops (the conventional continuous
        reading), computed from the global cumulative HIP curve (ADS
        family only). ``q`` must lie in (0, 1]; ``t_max`` bounds the
        horizon the quantile is taken against.
        """
        t_max = validate_t_max(t_max)
        self._require_kind("effective_diameter")
        sched = self._canonical_schedule(schedule)
        self._check_edges("effective_diameter")
        glob = self._hip_curve(t_max, sched).sum(axis=1)
        return float(self.family.hip_effective_diameter(glob, q))

    # ----------------------------------------------------- backend hooks
    @abc.abstractmethod
    def _accumulate_block(self, chunk: np.ndarray) -> None:
        """Scatter-max one undirected edge block int32[<=INGEST_BLOCK, 2]
        into ``self._regs`` via a donated jitted accumulate step."""

    @abc.abstractmethod
    def _place_rows(self, full: np.ndarray) -> jax.Array:
        """Place a full uint8[n_pad, r] row table under this backend's
        device layout (replicated locally / block-sharded on the mesh)."""

    @abc.abstractmethod
    def _propagate(self, regs: jax.Array, schedule: str) -> jax.Array:
        """One Algorithm 2 pass: D^t[x] = D^{t-1}[x] ∪̃ (∪̃_{xy∈E} D^{t-1}[y])."""

    @abc.abstractmethod
    def triangle_heavy_hitters(self, k: int, *, mode: str = "edge",
                               iters: int = 30,
                               ) -> tuple[float, np.ndarray, np.ndarray]:
        """Algorithms 4/5: (T̃ global, top-k values, top-k edge/vertex ids)."""

    # -------------------------------------------------------- persistence
    def _save_extra(self) -> dict:
        return {}

    def checkpoint_state(self) -> tuple[dict, dict]:
        """Return the ``(tree, extra)`` pair :meth:`save` would persist.

        The hook the failover runtime builds on: ``tree`` leaves are host
        ``np.ndarray``s (registers sliced to the n true rows, the edge
        list, the replica id set if placement installed one) and ``extra``
        is the manifest metadata including the ``m_ingested`` resume
        cursor. Feeding the pair to ``ckpt.AsyncCheckpointer.save`` takes
        an engine-format checkpoint *asynchronously* — ``engine.load``
        restores it at any shard count — which is how the coordinator
        (``repro.runtime.coordinator``, DESIGN.md §14) overlaps durability
        with ingest. The snapshot is consistent: call it between ingest
        blocks, not concurrently with one.
        """
        edges = self.edges
        tree = {"regs": np.asarray(self._regs)[: self.n]}
        if edges is not None:
            tree["edges"] = edges
        if self._replicas is not None:
            # the *id set* is the durable placement decision; rows are
            # re-gathered on load (fresh panel, any shard count/layout)
            tree["replica_ids"] = np.asarray(self._replicas.ids, np.int64)
        extra = {
            "format": ENGINE_FORMAT,
            "backend": self.backend,
            "n": self.n,
            "impl": self.impl,
            "layout": self.layout,
            "family": self.family.name,
            "m_ingested": self.m,
            "cfg": self.family.config_dict(self.cfg),
        }
        extra.update(self._save_extra())
        return tree, extra

    def save(self, path: str, step: int = 0) -> str:
        """Persist the accumulated sketch (registers + config + metadata).

        Layout is a ``repro.ckpt`` checkpoint: one .npy per leaf plus a
        manifest whose ``extra`` dict records the sketch family + config,
        backend, ingested edge count and plan metadata. Only the n true
        vertex rows
        are stored — padding is backend-dependent and reconstructed on
        load. Saving is legal *mid-stream*: the panel is a valid sketch of
        everything ingested so far, and a loaded engine resumes ingestion
        where this one stopped (registers and edge list pick up exactly).
        """
        from repro.ckpt.checkpoint import save_checkpoint
        tree, extra = self.checkpoint_state()
        return save_checkpoint(path, step, tree, extra=extra)
