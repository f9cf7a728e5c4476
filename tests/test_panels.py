"""t-hop panel cache: zero-pass re-queries, extension, invalidation.

Acceptance contract (ISSUE 4 / DESIGN.md §3c):
(a) repeated ``neighborhood(t_max)`` on an unchanged engine executes ZERO
    propagate passes — asserted through the plan layer's counters (the
    host-side ``propagate_pass`` event counter counts executions; the
    ``propagate`` trace counter separately shows no recompilation);
(b) a larger horizon extends the cached panel set incrementally
    (``t_max=5`` after ``t_max=3`` runs exactly passes 4-5);
(c) ingest/merge invalidate the cache via the ``version`` bump and the
    next query answers for the new panel;
(d) ``t_max``/``schedule`` are validated up front on BOTH backends
    (``t_max <= 0`` used to return empty arrays; the local backend used
    to silently ignore unknown schedule strings);
(e) panels beyond ``MAX_CACHED_PANELS`` are computed but not retained
    (the cache's memory bound).
"""
import numpy as np
import pytest

from repro import engine
from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.graph import generators as gen

CFG = HLLConfig(p=8)
BACKENDS = ["local", "sharded"]


@pytest.fixture(scope="module")
def graph():
    edges = gen.rmat(8, 8, seed=5)
    return edges, int(edges.max()) + 1


def _build(edges, n, backend):
    return engine.build(edges, n, CFG, backend=backend,
                        shards=1 if backend == "sharded" else None)


def _passes() -> int:
    return plans.event_counts().get("propagate_pass", 0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_repeat_query_executes_zero_propagate_passes(graph, backend):
    """The acceptance criterion: unchanged engine -> pure panel estimate."""
    edges, n = graph
    eng = _build(edges, n, backend)
    plans.reset_event_counts()
    l1, g1 = eng.neighborhood(3)
    assert _passes() == 2                     # t=1 is the accumulated table
    assert eng.panels_cached == 3
    l2, g2 = eng.neighborhood(3)
    assert _passes() == 2                     # zero additional passes
    np.testing.assert_array_equal(l1, l2)     # bit-identical panel answers
    np.testing.assert_array_equal(g1, g2)
    l_small, g_small = eng.neighborhood(2)    # shallower: prefix, no work
    assert _passes() == 2
    np.testing.assert_array_equal(l_small, l1[:2])
    np.testing.assert_array_equal(g_small, g1[:2])


@pytest.mark.parametrize("backend", BACKENDS)
def test_incremental_extension_runs_only_missing_passes(graph, backend):
    edges, n = graph
    eng = _build(edges, n, backend)
    plans.reset_event_counts()
    l3, _ = eng.neighborhood(3)
    assert _passes() == 2
    l5, _ = eng.neighborhood(5)               # extends: passes 4-5 only
    assert _passes() == 4
    assert eng.panels_cached == 5
    np.testing.assert_array_equal(l5[:3], l3)


def test_no_propagate_retrace_across_cached_queries(graph):
    """Trace counters: repeated/extended queries reuse ONE compiled pass."""
    edges, n = graph
    eng = _build(edges, n, "local")
    eng._plan_cache = plans.PlanCache(maxsize=32)
    plans.reset_trace_counts()
    eng.neighborhood(3)
    eng.neighborhood(3)
    eng.neighborhood(5)
    assert plans.trace_counts()["propagate"] == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_ingest_invalidates_panels_and_answers_track_new_epoch(graph,
                                                               backend):
    edges, n = graph
    half = len(edges) // 2
    eng = _build(edges[:half], n, backend)
    stale_l, _ = eng.neighborhood(2)
    assert eng.panels_cached == 2
    eng.ingest(edges[half:])
    assert eng.panels_cached == 0             # version bump dropped the set
    plans.reset_event_counts()
    fresh_l, fresh_g = eng.neighborhood(2)
    assert _passes() == 1                     # rematerialized for the epoch
    full_l, full_g = _build(edges, n, backend).neighborhood(2)
    np.testing.assert_array_equal(fresh_l, full_l)
    np.testing.assert_array_equal(fresh_g, full_g)
    assert not np.array_equal(stale_l, fresh_l)


def test_merge_invalidates_panels(graph):
    edges, n = graph
    half = len(edges) // 2
    eng = _build(edges[:half], n, "local")
    eng.neighborhood(2)
    assert eng.panels_cached == 2
    eng.merge(_build(edges[half:], n, "local"))
    assert eng.panels_cached == 0
    l, _ = eng.neighborhood(2)
    full_l, _ = _build(edges, n, "local").neighborhood(2)
    np.testing.assert_array_equal(l, full_l)


def test_memory_bound_panels_beyond_cap_not_retained(graph):
    edges, n = graph
    eng = _build(edges[:100], n, "local")
    eng.MAX_CACHED_PANELS = 3
    plans.reset_event_counts()
    eng.neighborhood(5)
    assert _passes() == 4
    assert eng.panels_cached == 3             # the bound, not the horizon
    eng.neighborhood(5)                       # cached prefix + 2 transient
    assert _passes() == 6


@pytest.mark.parametrize("backend", BACKENDS)
def test_t_max_validated(graph, backend):
    edges, n = graph
    eng = _build(edges[:50], n, backend)
    for bad in (0, -3, 1.5, "two", None):
        with pytest.raises(ValueError, match="t_max"):
            eng.neighborhood(bad)
    # np integers are fine
    l, g = eng.neighborhood(np.int64(2))
    assert l.shape == (2, n) and g.shape == (2,)


@pytest.mark.parametrize("backend", BACKENDS)
def test_schedule_validated_up_front_on_both_backends(graph, backend):
    edges, n = graph
    eng = _build(edges[:50], n, backend)
    with pytest.raises(ValueError, match="schedule"):
        eng.neighborhood(2, schedule="nope")
    for schedule in ("auto", "ring", "allgather"):
        l, _ = eng.neighborhood(2, schedule=schedule)
        assert l.shape == (2, n)


def test_local_schedules_share_one_panel_set(graph):
    """The local backend runs one dataflow: schedule strings share panels."""
    edges, n = graph
    eng = _build(edges[:100], n, "local")
    plans.reset_event_counts()
    l1, _ = eng.neighborhood(3, schedule="ring")
    assert _passes() == 2
    l2, _ = eng.neighborhood(3, schedule="allgather")
    assert _passes() == 2                     # same canonical key: no work
    np.testing.assert_array_equal(l1, l2)


def test_sharded_schedules_keyed_separately(graph):
    """Sharded ring/allgather panel sets cache under their own keys."""
    edges, n = graph
    eng = _build(edges[:100], n, "sharded")
    plans.reset_event_counts()
    l1, _ = eng.neighborhood(2, schedule="ring")
    assert _passes() == 1
    l2, _ = eng.neighborhood(2, schedule="allgather")
    assert _passes() == 2                     # different dataflow: re-runs
    np.testing.assert_array_equal(l1, l2)     # ... to bit-identical panels
    l3, _ = eng.neighborhood(2, schedule="auto")  # auto == ring: recompute
    assert _passes() == 3                     # (one set cached at a time)
    np.testing.assert_array_equal(l1, l3)


# ------------------------------------------- incremental propagate routing
#: (family, layout) cells of the local routing; ADS rows are byte-only
FAMILY_LAYOUTS = [("hll", "byte"), ("hll", "packed"), ("ads", "byte")]

#: slots per extend slice (2 * INGEST_BLOCK) in the routing tests, small so
#: the rmat(8, 8) graph (1,285 edges, buckets of 2,048 and 4,096 slots)
#: reaches every case
_SLICE_BLOCK = 16

#: base edges, ingest tails and the number of extends each case runs
ROUTING_CASES = {
    "fits": (900, [10, 6], 2),          # slots 1800 -> 1832 of 2048
    "looped_slices": (900, [100], 1),   # 200 slots: seven 32-slot slices
    "slice_overhangs_cap": (1010, [12], 1),  # 2020 + 32 > 2048 >= 2044
    "crosses_bucket": (1000, [100], 0),  # 2200 > 2048: a full rebuild
}


def _family_cfg(family):
    from repro.core import ads
    return ads.ADSConfig(p=8) if family == "ads" else CFG


def _routing_events() -> tuple[int, int]:
    ev = plans.event_counts()
    return ev.get("routing_full", 0), ev.get("routing_extend", 0)


@pytest.mark.parametrize("case", sorted(ROUTING_CASES))
@pytest.mark.parametrize("family,layout", FAMILY_LAYOUTS)
def test_extended_routing_bit_identical_to_fresh_engine(graph, family,
                                                        layout, case):
    """Ingest then neighborhood, alternately: every answer and cached panel
    equals a fresh engine's built from the same edges in one pass."""
    edges, n = graph
    cfg = _family_cfg(family)
    base, tails, extends = ROUTING_CASES[case]
    eng = engine.open(n, cfg, layout=layout, family=family)
    eng.INGEST_BLOCK = _SLICE_BLOCK
    eng.ingest(edges[:base])
    eng.neighborhood(3)
    plans.reset_event_counts()
    hi = base
    for k in tails:
        hi += k
        eng.ingest(edges[hi - k:hi])
        got_l, got_g = eng.neighborhood(3)
        fresh = engine.build(edges[:hi], n, cfg, layout=layout, family=family)
        want_l, want_g = fresh.neighborhood(3)
        np.testing.assert_array_equal(got_l, want_l)
        np.testing.assert_array_equal(got_g, want_g)
        for got, want in zip(eng._panel_set.panels, fresh._panel_set.panels,
                             strict=True):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rt = eng._prop_routing
    assert (rt.covered, rt.filled) == (hi, 2 * hi)
    assert rt.cap == plans.bucket(2 * (base if extends else hi))
    # each fresh engine builds one full routing; the writer only extends,
    # except where the tail crossed the bucket
    assert _routing_events() == (len(tails) + (extends == 0), extends)


def test_routing_extends_after_ingest_and_counts_events(graph):
    edges, n = graph
    eng = engine.open(n, CFG)
    plans.reset_event_counts()
    eng.ingest(edges[:900])
    eng.neighborhood(3)
    eng.ingest(edges[900:950])
    eng.neighborhood(3)
    assert _routing_events() == (1, 1)


def test_merge_drops_routing_to_a_full_build(graph):
    edges, n = graph
    eng = _build(edges[:900], n, "local")
    eng.neighborhood(2)
    eng.merge(_build(edges[900:950], n, "local"))
    assert eng._prop_routing is None
    plans.reset_event_counts()
    l, _ = eng.neighborhood(2)
    assert _routing_events() == (1, 0)
    np.testing.assert_array_equal(l, _build(edges[:950], n,
                                            "local").neighborhood(2)[0])


def test_ingest_alone_leaves_routing_untouched(graph):
    """Ingest does no routing work: no event, no routing span; the next
    propagate appends every block ingested since in one extend."""
    edges, n = graph
    eng = _build(edges[:900], n, "local")
    eng.neighborhood(2)
    rt = eng._prop_routing
    plans.reset_event_counts()
    plans.reset_span_stats()
    for lo in range(900, 1000, 10):
        eng.ingest(edges[lo:lo + 10])
    assert eng._prop_routing is rt
    assert _routing_events() == (0, 0)
    assert not [k for k in plans.span_stats() if "routing" in k]
    eng.neighborhood(2)
    assert _routing_events() == (0, 1)
    assert plans.span_stats()["ds.engine.routing.extend"]["count"] == 1
    assert (rt.covered, rt.filled) == (900, 1800)   # the old record stays


def test_extend_in_warmed_bucket_compiles_nothing(graph):
    """The full build warms the extend plan of its bucket: an extend in the
    same bucket adds nothing to the trace counters."""
    edges, n = graph
    eng = _build(edges[:900], n, "local")
    eng._plan_cache = plans.PlanCache(maxsize=32)
    eng.neighborhood(3)
    plans.reset_trace_counts()
    eng.ingest(edges[900:950])
    eng.neighborhood(3)
    assert plans.trace_counts() == {}
    assert eng._prop_routing.covered == 950
