"""Backend-independent query planning: normalization, bucketing, plan cache.

A *query plan* is a jitted callable specialized to a (query kind, shape
bucket, sketch config, kernel impl, backend, family) combination; this
module (DESIGN.md §3b) owns everything about plans that is independent of
any one engine. It is sketch-family-agnostic (DESIGN.md §13): everything
family-specific — estimator tails, pair MLE math — is reached through the
engine's resolved :class:`~repro.kernels.registry.KernelSet` and the
family registry, never by importing ``repro.core`` symbols (enforced by
``tools/check_layering.py``). Concretely:

* **Input normalization** — :func:`normalize_sets` / :func:`normalize_pairs`
  turn ragged client input into padded, masked, power-of-two-bucketed host
  arrays, validating vertex ids against the engine's universe ``[0, n)``
  (out-of-range ids raise ``ValueError`` like ``ingest`` does, instead of
  silently clamping through a jnp gather).
* **Shape bucketing** — :func:`bucket` rounds batch dimensions up to the
  next power of two, so jittering client batch sizes reuse O(log max-batch)
  compiled programs per query kind instead of retracing per call.
* **Plan construction** — the ``build_*_plan`` builders close over nothing
  engine-specific (config and a hashable :class:`~repro.kernels.registry.
  KernelSet` only), which is what makes the cache shareable across engines.
* **The shared cache** — :class:`PlanCache` is an LRU-bounded map from
  :class:`PlanKey` to compiled plan, shared by every engine with identical
  ``(cfg, impl, backend)`` through :func:`global_cache` (engines used to
  each hold a private unbounded dict).

Every plan body bumps a module-level *trace counter* when it is traced
(python side effects run once per trace), so tests and the serving stats
can assert "no retrace within a shape bucket" and "N clients served by
O(log N) compiled programs" directly — see :func:`trace_counts`.

Trace counters count *compiled programs*; some invariants are about
*executions* (the t-hop panel cache promises zero propagate passes on an
unchanged engine — a cached program re-run would not retrace). Those are
counted host-side via the companion *event counters*
(:func:`record_event` / :func:`event_counts`): engines bump
``"propagate_pass"`` once per propagate pass they actually execute, so
tests assert the panel cache by both counters (DESIGN.md §3c).

Host *spans* (:func:`span`) time the host side of each layer boundary —
serving drains, ingest preparation, query padding, device fetches, the
propagation routing rebuild. A span is a ``jax.profiler.TraceAnnotation``
(near-free with no profiler session; on the profiler's host plane, on the
device ops' clock, when one runs) that also adds its host-clock duration
to a per-name ``[count, total]`` entry under the counter lock, read by
:func:`span_stats`. Every span name starts with ``ds.``, so a trace
reduction selects the program's spans by prefix (DESIGN.md §3b).

Every plan jit carries a stable name (:func:`_plan_jit`): a profile's
``XLA Modules`` line reads ``jit_plan_union``, ``jit_plan_propagate``, ...
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import registry

__all__ = [
    "bucket", "split_sets", "pad_sets", "split_pairs", "pad_pairs",
    "normalize_sets", "normalize_pairs", "pad_routing",
    "require_integer_ids", "PlanKey",
    "PlanCache", "global_cache", "trace_counts", "reset_trace_counts",
    "record_trace", "record_event", "event_counts", "reset_event_counts",
    "span", "span_stats", "reset_span_stats",
    "build_degrees_plan", "build_union_plan",
    "build_intersection_plan", "build_mixed_plan", "build_merge_plan",
    "build_propagate_plan", "build_routing_extend_plan",
    "build_replica_gather_plan",
    "build_hip_delta_plan",
]


def bucket(size: int, minimum: int = 8) -> int:
    """Next power-of-two shape bucket (>= minimum) for plan caching."""
    return max(minimum, 1 << max(int(size) - 1, 0).bit_length())


# ------------------------------------------------------------ normalization
def require_integer_ids(arr: np.ndarray, what: str) -> None:
    """Raise ValueError unless ``arr`` has an integer (or bool-free) dtype.

    Vertex ids arrive from clients as arbitrary array-likes; a float array
    cast with ``astype(int)`` silently truncates (3.7 -> 3), answering the
    query for a *different vertex*. Every id-consuming entry point
    (``ingest``, :func:`split_sets`, :func:`split_pairs`, ``from_regs``)
    rejects non-integer dtypes here instead.
    """
    if arr.size and arr.dtype.kind not in "iu":
        raise ValueError(
            f"{what} must have an integer dtype; got {arr.dtype} — float "
            f"vertex ids would be silently truncated (e.g. 3.7 -> 3)")


def _validate_ids(arr: np.ndarray, n: int | None, query: str) -> None:
    """Raise ValueError for vertex ids outside [0, n) — mirror of ingest.

    Checked host-side *before* the int32 cast and the device gather: jnp
    gathers clamp out-of-range indices, which would silently answer the
    query for a different vertex.
    """
    if n is None or arr.size == 0:
        return
    lo, hi = int(arr.min()), int(arr.max())
    if lo < 0 or hi >= n:
        raise ValueError(
            f"{query} got vertex ids [{lo}, {hi}] outside the engine's "
            f"universe [0, {n}); jnp gathers would silently clamp them")


def split_sets(vertex_sets, n: int | None = None,
               ) -> tuple[list[np.ndarray], bool]:
    """Parse union-query input into (list of 1-D int64 id arrays, scalar).

    Accepts a single 1-D array of vertex ids (one set -> scalar result), a
    list/tuple of 1-D arrays (ragged batch), or a 2-D array (rectangular
    batch). Ids are validated against ``[0, n)`` when ``n`` is given. This
    is the client-side half of :func:`normalize_sets`, split out so a
    server can validate/parse per request and pad per coalesced batch.
    """
    if isinstance(vertex_sets, (list, tuple)):
        raws = [np.asarray(s).ravel() for s in vertex_sets]
        for s in raws:
            require_integer_ids(s, "union_size vertex ids")
        sets = [s.astype(np.int64) for s in raws]
        scalar = False
    else:
        arr = np.asarray(vertex_sets)
        require_integer_ids(arr, "union_size vertex ids")
        if arr.ndim == 1:
            sets, scalar = [arr.astype(np.int64)], True
        elif arr.ndim == 2:
            sets, scalar = list(arr.astype(np.int64)), False
        else:
            raise ValueError(f"vertex_sets must be 1-D, 2-D or a list "
                             f"of 1-D arrays, got ndim={arr.ndim}")
    if not sets:
        raise ValueError("union_size needs at least one vertex set")
    for s in sets:
        _validate_ids(s, n, "union_size")
    return sets, scalar


def pad_sets(sets: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Pad parsed id sets to bucketed (ids int32[B, L], mask bool[B, L]).

    Padding slots are masked out, never merged — a padding slot treated as
    a real row would gather vertex 0's registers into the union.
    """
    longest = max((len(s) for s in sets), default=1)
    ids = np.zeros((bucket(len(sets)), bucket(max(longest, 1))), np.int32)
    mask = np.zeros(ids.shape, bool)
    for i, s in enumerate(sets):
        ids[i, : len(s)] = s
        mask[i, : len(s)] = True
    return ids, mask


def normalize_sets(vertex_sets, n: int | None = None,
                   ) -> tuple[np.ndarray, np.ndarray, int, bool]:
    """Normalize union-query input to bucketed (ids, mask, n_real, scalar).

    ``split_sets`` (parse + id validation) followed by ``pad_sets``
    (power-of-two bucketing with validity masks).
    """
    sets, scalar = split_sets(vertex_sets, n)
    ids, mask = pad_sets(sets)
    return ids, mask, len(sets), scalar


def split_pairs(pairs, n: int | None = None) -> tuple[np.ndarray, bool]:
    """Parse pair-query input into (validated int64[B, 2] ids, scalar).

    The client-side half of :func:`normalize_pairs` (mirror of
    :func:`split_sets`): shape and id-range validation happens here, so a
    server can reject a malformed request on the calling thread and pad
    per coalesced batch.
    """
    raw = np.asarray(pairs)
    require_integer_ids(raw, "intersection_size pair ids")
    arr = raw.astype(np.int64)
    scalar = arr.ndim == 1
    if scalar:
        arr = arr[None]
    if arr.ndim != 2 or arr.shape[1] != 2:
        raise ValueError(f"pairs must have shape (B, 2), got {arr.shape}")
    _validate_ids(arr, n, "intersection_size")
    return arr, scalar


def pad_pairs(arr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Pad parsed (B, 2) pairs to bucketed (ids int32[B', 2], mask[B'])."""
    n_real = arr.shape[0]
    out = np.zeros((bucket(n_real), 2), np.int32)
    out[:n_real] = arr
    mask = np.zeros((out.shape[0],), bool)
    mask[:n_real] = True
    return out, mask


def normalize_pairs(pairs, n: int | None = None,
                    ) -> tuple[np.ndarray, np.ndarray, int, bool]:
    """Normalize pair-query input to bucketed ((B, 2) ids, mask, n, scalar).

    Ids are validated against ``[0, n)`` when ``n`` is given (ValueError,
    like ``ingest`` — never a silent clamp through the register gather).
    """
    arr, scalar = split_pairs(pairs, n)
    out, mask = pad_pairs(arr)
    return out, mask, arr.shape[0], scalar


def pad_routing(src: np.ndarray, dst: np.ndarray, cap: int | None = None,
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a directed edge routing to a power-of-two shape bucket.

    Returns ``(src int32[E'], dst int32[E'], mask bool[E'])`` with E' =
    ``cap``, by default ``bucket(len(src))``. This is what keeps
    propagation plans shape-bucketed: edge counts that land in the same
    bucket share one compiled program instead of retracing per distinct
    edge count (DESIGN.md §3c); padding slots are masked out inside
    :func:`build_propagate_plan`.
    """
    m = len(src)
    if cap is None:
        cap = bucket(max(m, 1))
    src_p = np.zeros((cap,), np.int32)
    dst_p = np.zeros((cap,), np.int32)
    mask = np.zeros((cap,), bool)
    src_p[:m] = src
    dst_p[:m] = dst
    mask[:m] = True
    return src_p, dst_p, mask


# ------------------------------------------------------------ trace counter
_TRACE_LOCK = threading.Lock()
_TRACE_COUNTS: dict[str, int] = {}


def record_trace(query: str) -> None:
    """Bump the trace counter for ``query`` (call from inside plan bodies).

    Python side effects inside a jitted function body execute once per
    trace, so this counts *compiled programs*, not calls — the quantity
    the shape-bucketing design bounds to O(log batch) per query kind.
    """
    with _TRACE_LOCK:
        _TRACE_COUNTS[query] = _TRACE_COUNTS.get(query, 0) + 1


def trace_counts() -> dict[str, int]:
    """Snapshot of {query kind: number of traces since the last reset}."""
    with _TRACE_LOCK:
        return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    """Zero the trace counters (test fixtures; serving stats windows)."""
    with _TRACE_LOCK:
        _TRACE_COUNTS.clear()


# ------------------------------------------------------------ event counter
_EVENT_COUNTS: dict[str, int] = {}


def record_event(event: str, count: int = 1) -> None:
    """Add ``count`` (default 1) to the host-side *execution* counter.

    Complement of :func:`record_trace`: trace counters count compiled
    programs, event counters count host-observed executions — engines bump
    ``"propagate_pass"`` once per Algorithm 2 pass actually run, which is
    how the t-hop panel cache's "zero passes on an unchanged engine"
    guarantee is asserted (a cached program re-run would never retrace).
    Quantities are counted the same way: the sharded ingest adds its
    routed slots to ``"route_slots"`` and their padding to
    ``"route_padded"``.
    """
    with _TRACE_LOCK:
        _EVENT_COUNTS[event] = _EVENT_COUNTS.get(event, 0) + int(count)


def event_counts() -> dict[str, int]:
    """Snapshot of {event: executions since the last reset}."""
    with _TRACE_LOCK:
        return dict(_EVENT_COUNTS)


def reset_event_counts() -> None:
    """Zero the event counters (test fixtures; serving stats windows)."""
    with _TRACE_LOCK:
        _EVENT_COUNTS.clear()


# ------------------------------------------------------------- host spans
_SPAN_STATS: dict[str, list] = {}  # name -> [count, total seconds]


class span:
    """Time a host stretch as a profiler span and in :func:`span_stats`.

    ``with span("ds.engine.ingest"): ...`` opens
    ``jax.profiler.TraceAnnotation(name, **meta)`` (``meta`` shows as the
    event's arguments in a profile) and, on exit, adds the stretch's
    ``time.perf_counter`` duration to the name's entry. Spans nest; each
    counts its own whole duration. After exit, ``start`` and ``seconds``
    hold the stretch's start (``perf_counter``) and length, for callers
    that keep their own window totals (``QueryServer.stats()``).
    """

    __slots__ = ("name", "start", "seconds", "_ann")

    def __init__(self, name: str, **meta):
        self.name = name
        self.start = self.seconds = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, typ, val, tb) -> bool:
        self.seconds = sec = time.perf_counter() - self.start
        self._ann.__exit__(typ, val, tb)
        with _TRACE_LOCK:
            try:
                entry = _SPAN_STATS[self.name]
                entry[0] += 1
                entry[1] += sec
            except KeyError:
                _SPAN_STATS[self.name] = [1, sec]
        return False


def span_stats() -> dict[str, dict]:
    """Snapshot of {span name: {"count", "total_ms"}} since the last reset."""
    with _TRACE_LOCK:
        return {k: {"count": c, "total_ms": t * 1e3}
                for k, (c, t) in _SPAN_STATS.items()}


def reset_span_stats() -> None:
    """Zero the span totals (test fixtures)."""
    with _TRACE_LOCK:
        _SPAN_STATS.clear()


# -------------------------------------------------------------- plan cache
@dataclass(frozen=True)
class PlanKey:
    """Identity of a compiled query plan.

    Two engines produce bit-identical answers from the same registers iff
    they agree on all of these coordinates, so the cache is shared exactly
    at this granularity:

    Attributes:
      query: query kind ("degrees" | "union" | "intersection" | ...).
      bucket: the padded/bucketed input shape the plan was built for.
      cfg: the sketch config (hashable frozen dataclass) — or ``None``
        for plans whose body never consults it.
      impl: kernel implementation name ("ref" | "pallas" | ...).
      backend: engine backend ("local" | "sharded").
      layout: register-panel layout the plan's panels use ("byte" |
        "packed", DESIGN.md §11) — a packed plan gathers half-width
        rows, so layouts must never share a compiled program.
      family: sketch-family registry coordinate ("hll" | "ads",
        DESIGN.md §13) — families interpret the same registers through
        different estimators, so they never share a compiled program
        (configs differ by type anyway; the explicit coordinate keeps
        the cache key self-describing for config-free plans).
      extra: any further static specialization (method/iters for the MLE,
        shard count for mesh-closed plans, ...).
    """

    query: str
    bucket: tuple = ()
    cfg: object = None
    impl: str = "ref"
    backend: str = "local"
    layout: str = "byte"
    extra: tuple = ()
    family: str = "hll"


class PlanCache:
    """LRU-bounded, thread-safe cache from :class:`PlanKey` to plan.

    One instance (:func:`global_cache`) is shared by every engine in the
    process, replacing the per-engine unbounded dicts: engines with
    identical ``(cfg, impl, backend)`` reuse each other's compiled plans,
    and the LRU bound keeps a long-lived serving process from accumulating
    plans for shape buckets it no longer sees. Eviction drops the python
    reference; XLA executables are garbage-collected with their jitted
    wrapper.
    """

    def __init__(self, maxsize: int = 256):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self._maxsize = int(maxsize)
        self._entries: OrderedDict[PlanKey, object] = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def maxsize(self) -> int:
        """The LRU bound (entries beyond it evict least-recently-used)."""
        return self._maxsize

    def __len__(self) -> int:
        """Number of cached plans."""
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: PlanKey) -> bool:
        """Whether ``key`` is cached (does not refresh LRU order)."""
        with self._lock:
            return key in self._entries

    def get(self, key: PlanKey, builder):
        """Return the plan for ``key``, building (and caching) on miss.

        ``builder`` is a zero-arg callable producing the plan; it runs
        under the cache lock (builders only *create* jitted callables —
        compilation happens lazily at first call, outside the lock).
        """
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return fn
            self.misses += 1
            fn = builder()
            self._entries[key] = fn
            while len(self._entries) > self._maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
            return fn

    def clear(self) -> None:
        """Drop every cached plan (stats counters are kept)."""
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        """Snapshot {hits, misses, evictions, size, maxsize}."""
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "size": len(self._entries),
                    "maxsize": self._maxsize}


_GLOBAL_CACHE = PlanCache()


def global_cache() -> PlanCache:
    """The process-wide plan cache engines share by default."""
    return _GLOBAL_CACHE


# ------------------------------------------------------------ plan builders
def _plan_jit(kind: str, fn, **jit_kw):
    """``jax.jit(fn)`` under the stable program name ``plan_<kind>``.

    XLA names a program after the jitted function, so every plan body
    (all named ``fn``) would trace as ``jit_fn``; renaming it makes a
    profile's ``XLA Modules`` line and every op's module say which plan
    ran (``jit_plan_propagate``).
    """
    fn.__name__ = fn.__qualname__ = f"plan_{kind}"
    return jax.jit(fn, **jit_kw)


def build_degrees_plan(cfg, kernels):
    """Plan: per-row degree estimates d̃(x) over the full register table."""
    def fn(regs):
        record_trace("degrees")
        return kernels.estimate_rows(regs, cfg)
    return _plan_jit("degrees", fn)


def _union_body(regs, ids, mask, cfg, kernels):
    """Shared fused-union body (per-kind and mixed plans trace the same)."""
    return kernels.union_estimate(regs, ids, mask, cfg)


def _intersection_body(regs, pairs, mask, cfg, kernels, method, iters):
    """Shared fused-intersection body: stats kernel + estimator tail.

    The estimator tail is the *family's* (``estimate_from_pair_stats``,
    resolved by registry name) — the plan body never imports family math.
    """
    stats, sz = kernels.intersection_stats(regs, pairs, cfg)
    fam = registry.family(kernels.family)
    est = fam.estimate_from_pair_stats(stats, sz, cfg, method, iters)
    return jnp.where(mask, est, 0.0)


def build_union_plan(cfg, kernels, replicas: bool = False):
    """Plan: batched |∪ N(x)| over bucketed (ids, mask) set panels.

    Fused (DESIGN.md §10): the kernel set's ``union_estimate`` gathers,
    max-merges and reduces each set row in one pass — the merged register
    panels the old two-pass plan materialized between its gather and
    estimate stages never exist. The ref impl is the bit-checked oracle
    for that old path (same ops, same order).

    With ``replicas=True`` the callable takes ``(regs, rep, ids, mask)``:
    the replica panel ``rep`` (hot-vertex rows, DESIGN.md §12) is
    concatenated below the register table and ``ids`` arrive pre-remapped
    by :func:`repro.engine.placement.remap_ids` — the kernel gathers
    byte-identical rows from replica slots, so answers are bitwise equal
    to the replica-free plan. Traced as ``union_rep`` (its own
    compiled-program counter; the O(log batch) per-kind trace bound
    stays assertable per variant).
    """
    if replicas:
        def fn(regs, rep, ids, mask):
            record_trace("union_rep")
            table = jnp.concatenate([regs, rep], axis=0)
            return _union_body(table, ids, mask, cfg, kernels)
    else:
        def fn(regs, ids, mask):
            record_trace("union")
            return _union_body(regs, ids, mask, cfg, kernels)
    return _plan_jit("union_rep" if replicas else "union", fn)


def build_intersection_plan(cfg, kernels, method: str, iters: int,
                            replicas: bool = False):
    """Plan: batched T̃(xy) over bucketed (pairs, mask) panels.

    Fused (DESIGN.md §10): ``intersection_stats`` gathers both endpoint
    sketches per pair and emits the Eq. 19 histograms plus the (s, z)
    panels in one pass; the MLE / inclusion-exclusion tail runs from the
    statistics alone. ``method="mle"`` is Ertl's maximum-likelihood
    estimator; ``"ie"`` the inclusion-exclusion baseline (Eq. 18). Both
    are static plan coordinates (they change the traced program).

    ``replicas=True`` mirrors :func:`build_union_plan`: the callable takes
    ``(regs, rep, pairs, mask)`` with pair endpoints pre-remapped onto
    replica slots; traced as ``intersection_rep``.
    """
    if replicas:
        def fn(regs, rep, pairs, mask):
            record_trace("intersection_rep")
            table = jnp.concatenate([regs, rep], axis=0)
            return _intersection_body(table, pairs, mask, cfg, kernels,
                                      method, iters)
    else:
        def fn(regs, pairs, mask):
            record_trace("intersection")
            return _intersection_body(regs, pairs, mask, cfg, kernels,
                                      method, iters)
    return _plan_jit("intersection_rep" if replicas else "intersection", fn)


def build_mixed_plan(cfg, kernels, kinds: tuple, method: str, iters: int,
                     replicas: bool = False):
    """Plan: one program answering a degrees+union+intersection micro-batch.

    ``kinds`` (a static subset of ``("degrees", "union", "intersection")``)
    selects which sub-queries the traced program computes; the callable
    always takes ``(regs, u_ids, u_mask, p_ids, p_mask)`` — panels for
    absent kinds are dummies the trace never touches. Each sub-answer is
    computed by the same fused body as its per-kind plan, so a coalesced
    mixed batch is bit-identical to per-kind calls while costing ONE
    compiled-program launch instead of ``len(kinds)`` (DESIGN.md §10).

    ``replicas=True`` adds the replica panel argument (``(regs, rep,
    u_ids, u_mask, p_ids, p_mask)``) for the gather kinds; the degrees
    sub-answer still scans only the true register table — replica rows
    are copies and must not be double-counted. Traced as ``mixed_rep``.
    """
    def compute(table, regs, u_ids, u_mask, p_ids, p_mask):
        out = {}
        if "degrees" in kinds:
            out["degrees"] = kernels.estimate_rows(regs, cfg)
        if "union" in kinds:
            out["union"] = _union_body(table, u_ids, u_mask, cfg, kernels)
        if "intersection" in kinds:
            out["intersection"] = _intersection_body(
                table, p_ids, p_mask, cfg, kernels, method, iters)
        return out

    if replicas:
        def fn(regs, rep, u_ids, u_mask, p_ids, p_mask):
            record_trace("mixed_rep")
            table = jnp.concatenate([regs, rep], axis=0)
            return compute(table, regs, u_ids, u_mask, p_ids, p_mask)
    else:
        def fn(regs, u_ids, u_mask, p_ids, p_mask):
            record_trace("mixed")
            return compute(regs, regs, u_ids, u_mask, p_ids, p_mask)
    return _plan_jit("mixed_rep" if replicas else "mixed", fn)


def build_replica_gather_plan():
    """Plan: gather the replica panel rows ``regs[ids]`` (hot-vertex rows).

    Used by ``SketchEngine.replicate``/refresh (DESIGN.md §12): ``ids`` is
    the padded hot-vertex id vector, the output the uint8[K_pad, w]
    replica panel placed by the backend (replicated across shards). Pure
    gather — layout-agnostic byte copies, so refreshed replicas are
    byte-identical to their owner rows at the gathered version.
    """
    def fn(regs, ids):
        record_trace("replica_gather")
        return regs[ids]
    return _plan_jit("replica_gather", fn)


def build_merge_plan(layout: str = "byte"):
    """Plan: lane-wise register max with the left panel donated.

    Layout-aware: packed panels merge nibble-wise through
    ``packing.merge_rows`` — a byte-wise max on packed bytes would pick
    one whole byte and drop the larger of the two 4-bit lanes the other
    operand holds (DESIGN.md §11).
    """
    from repro.kernels import packing

    def fn(mine, theirs):
        record_trace("merge")
        return packing.merge_rows(mine, theirs, layout=layout)
    return _plan_jit("merge", fn, donate_argnums=(0,))


def build_hip_delta_plan(kernels):
    """Plan: batch-HIP per-row increments between two hop panels.

    Takes ``(prev, cur)`` — the D^{t-1} and D^t register panels — and
    returns float32[N] summed inverse change probabilities (the ADS
    family's ``hip_delta`` op; DESIGN.md §13). The engine folds these
    into the cached cumulative HIP curve beside the t-hop panel cache.
    """
    def fn(prev, cur):
        record_trace("hip_delta")
        return kernels.hip_delta(prev, cur)
    return _plan_jit("hip_delta", fn)


def build_propagate_plan(kernels):
    """Plan: one Algorithm 2 gather-max pass over a bucketed edge routing.

    Takes ``(regs, src, dst, mask)`` as produced by :func:`pad_routing`:
    the routing is padded to a power-of-two shape bucket (the plan key
    carries the bucket), so engines whose edge counts grow under streaming
    retrace only when the *bucket* changes, not per distinct edge count.
    Masked-out slots route ``(0, 0)``, a self-merge no-op under register
    max.
    """
    def fn(regs, src, dst, mask):
        record_trace("propagate")
        return kernels.propagate(regs, src, dst, mask=mask)
    return _plan_jit("propagate", fn)


def build_routing_extend_plan():
    """Plan: write one fixed-size slice of slots into a padded routing.

    Takes the routing ``(src, dst, mask)`` of :func:`pad_routing`, the
    first slot to write ``at`` and a slice ``(s_src, s_dst, s_mask)`` of S
    slots, its tail padded with ``(0, 0, False)``; returns the routing
    with slots ``[at, at + S)`` replaced. Slots past the routing's end
    are dropped, never clamped back below ``at`` as a dynamic update
    slice would be, so the slice may overhang the bucket as long as its
    real slots fit. The inputs are not donated: a snapshot may still hold
    them. Keyed by ``(cap, S)``; ``at`` is traced, so one program serves
    every offset.
    """
    def fn(src, dst, mask, at, s_src, s_dst, s_mask):
        record_trace("routing_extend")
        idx = at + jnp.arange(s_src.shape[0], dtype=jnp.int32)

        def put(a, v):
            return a.at[idx].set(v, mode="drop", indices_are_sorted=True,
                                 unique_indices=True)
        return put(src, s_src), put(dst, s_dst), put(mask, s_mask)
    return _plan_jit("routing_extend", fn)
