"""Query-plan layer + kernel registry: caching, validation, capabilities.

Acceptance contract (ISSUE 3 / DESIGN.md §3b):
(a) no retrace within a shape bucket — asserted through the plan layer's
    trace counters (a python side effect in the plan body runs once per
    trace, so the counter counts *compiled programs*, not calls);
(b) plans are shared across engines with identical (cfg, impl, backend)
    and isolated across differing coordinates;
(c) the cache is LRU-bounded;
(d) query-side vertex ids are validated against [0, n) exactly like
    ``ingest`` (ValueError, never a silent clamp through a jnp gather);
(e) the kernel registry resolves capability-checked kernel sets at engine
    construction — unknown impls fail up front naming the registered
    ones, and the beta-estimator fallback is recorded explicitly;
(f) Pallas interpret mode is resolved per call, not at import time.
"""
import jax
import numpy as np
import pytest

from repro import engine
from repro.core import hll
from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.graph import generators as gen
from repro.kernels import registry

CFG = HLLConfig(p=8)


@pytest.fixture(scope="module")
def graph():
    edges = gen.rmat(8, 8, seed=5)
    return edges, int(edges.max()) + 1


@pytest.fixture()
def isolated(graph):
    """A local engine wired to a private plan cache (no cross-test state)."""
    edges, n = graph
    eng = engine.build(edges, n, CFG, backend="local")
    eng._plan_cache = plans.PlanCache(maxsize=32)
    plans.reset_trace_counts()
    return eng


# ---------------------------------------------------------------- bucketing
def test_bucket_rounds_up_to_pow2():
    assert [plans.bucket(s) for s in (0, 1, 8, 9, 100)] == [8, 8, 8, 16, 128]
    assert plans.bucket(3, minimum=2) == 4


# ----------------------------------------------------------- trace counting
def test_no_retrace_within_shape_bucket(isolated, graph):
    edges, _ = graph
    isolated.intersection_size(edges[:9])
    isolated.intersection_size(edges[:12])   # same bucket of 16
    isolated.intersection_size(edges[:16])   # still bucket 16
    assert plans.trace_counts()["intersection"] == 1
    isolated.intersection_size(edges[:30])   # bucket 32 -> one more program
    assert plans.trace_counts()["intersection"] == 2
    sets = [np.arange(3), np.arange(5)]
    isolated.union_size(sets)
    isolated.union_size([np.arange(2)] * 4)  # same (8, 8) bucket
    assert plans.trace_counts()["union"] == 1


def test_degrees_plan_traced_once(isolated):
    isolated.degrees()
    isolated.degrees()
    assert plans.trace_counts()["degrees"] == 1
    assert isolated.plan_cache.stats()["hits"] >= 1


# ------------------------------------------------------------- cache sharing
def test_plan_cache_shared_across_engines(graph):
    """Identical (cfg, impl, backend) -> the second engine compiles nothing."""
    edges, n = graph
    cache = plans.PlanCache(maxsize=32)
    a = engine.build(edges, n, CFG, backend="local")
    b = engine.build(edges[: len(edges) // 2], n, CFG, backend="local")
    a._plan_cache = b._plan_cache = cache
    plans.reset_trace_counts()
    ra = a.intersection_size(edges[:10])
    misses_after_a = cache.stats()["misses"]
    rb = b.intersection_size(edges[:10])
    assert cache.stats()["misses"] == misses_after_a  # pure hit for b
    assert plans.trace_counts()["intersection"] == 1
    # same plan, different register tables: answers differ as they should
    assert ra.shape == rb.shape and not np.array_equal(ra, rb)


def test_plan_cache_isolated_by_coordinates(graph):
    """impl/backend/cfg are key coordinates — no false sharing."""
    edges, n = graph
    cache = plans.PlanCache(maxsize=32)
    a = engine.build(edges[:200], n, CFG, backend="local", impl="ref")
    b = engine.build(edges[:200], n, CFG, backend="local", impl="pallas")
    c = engine.build(edges[:200], n, HLLConfig(p=9), backend="local")
    for e in (a, b, c):
        e._plan_cache = cache
    a.degrees()
    m1 = cache.stats()["misses"]
    b.degrees()
    m2 = cache.stats()["misses"]
    c.degrees()
    m3 = cache.stats()["misses"]
    assert m1 < m2 < m3  # each coordinate set compiled its own plan


def test_plan_cache_lru_eviction():
    cache = plans.PlanCache(maxsize=2)
    k1 = plans.PlanKey(query="q", bucket=(1,))
    k2 = plans.PlanKey(query="q", bucket=(2,))
    k3 = plans.PlanKey(query="q", bucket=(3,))
    cache.get(k1, lambda: "p1")
    cache.get(k2, lambda: "p2")
    cache.get(k1, lambda: "p1b")        # refresh k1 -> k2 becomes LRU
    cache.get(k3, lambda: "p3")         # evicts k2
    assert len(cache) == 2
    assert k1 in cache and k3 in cache and k2 not in cache
    assert cache.stats()["evictions"] == 1
    # evicted plans rebuild on demand
    assert cache.get(k2, lambda: "p2-rebuilt") == "p2-rebuilt"
    with pytest.raises(ValueError, match="maxsize"):
        plans.PlanCache(maxsize=0)


def test_engines_default_to_process_global_cache(graph):
    edges, n = graph
    a = engine.build(edges[:50], n, CFG)
    b = engine.build(edges[:50], n, CFG)
    assert a.plan_cache is b.plan_cache is plans.global_cache()


# ------------------------------------------------------------- id validation
def test_union_rejects_out_of_universe_ids(graph, isolated):
    edges, n = graph
    with pytest.raises(ValueError, match="universe"):
        isolated.union_size([np.array([0, n])])
    with pytest.raises(ValueError, match="universe"):
        isolated.union_size(np.array([-1, 2]))
    with pytest.raises(ValueError, match="universe"):
        isolated.union_size(np.array([[0, 1], [1, n + 7]]))


def test_intersection_rejects_out_of_universe_ids(graph, isolated):
    edges, n = graph
    with pytest.raises(ValueError, match="universe"):
        isolated.intersection_size((0, n))
    with pytest.raises(ValueError, match="universe"):
        isolated.intersection_size(np.array([[0, 1], [-3, 2]]))


def test_from_regs_rejects_out_of_universe_edges(graph):
    """Triangle/neighborhood gathers replay `edges` — validate at entry."""
    edges, n = graph
    rows = np.zeros((n, CFG.r), np.uint8)
    bad = np.array([[0, n + 1]], np.int32)
    with pytest.raises(ValueError, match="universe"):
        engine.LocalEngine.from_regs(rows, n, CFG, edges=bad)
    with pytest.raises(ValueError, match="universe"):
        engine.ShardedEngine.from_regs(rows, n, CFG, edges=bad, shards=1)


def test_normalize_helpers_validate_and_pad():
    ids, mask, n_real, scalar = plans.normalize_sets([np.arange(3)], n=10)
    assert ids.shape == (8, 8) and mask[:1, :3].all() and not scalar
    assert n_real == 1
    with pytest.raises(ValueError, match="at least one"):
        plans.normalize_sets([], n=10)
    with pytest.raises(ValueError, match="shape"):
        plans.normalize_pairs(np.arange(6).reshape(2, 3), n=10)


def test_float_vertex_ids_rejected_not_truncated(graph, isolated):
    """ingest/queries reject float ids instead of truncating 3.7 -> 3."""
    edges, n = graph
    with pytest.raises(ValueError, match="integer dtype"):
        isolated.ingest(np.array([[0.5, 1.7]]))
    with pytest.raises(ValueError, match="integer dtype"):
        isolated.ingest(edges.astype(np.float32))
    with pytest.raises(ValueError, match="integer dtype"):
        isolated.union_size([np.array([3.7])])
    with pytest.raises(ValueError, match="integer dtype"):
        isolated.union_size(np.array([[0.0, 1.0]]))
    with pytest.raises(ValueError, match="integer dtype"):
        isolated.intersection_size(np.array([[0.5, 2.0]]))
    with pytest.raises(ValueError, match="integer dtype"):
        isolated.intersection_size((0.5, 2))
    with pytest.raises(ValueError, match="integer dtype"):
        plans.split_sets([np.array([1.5, 2.0])], n)
    with pytest.raises(ValueError, match="integer dtype"):
        plans.split_pairs(np.array([[1.5, 2.0]]), n)
    # from_regs edge lists go through the same gate
    rows = np.zeros((n, CFG.r), np.uint8)
    with pytest.raises(ValueError, match="integer dtype"):
        engine.LocalEngine.from_regs(rows, n, CFG,
                                     edges=np.array([[0.0, 1.5]]))
    # integer input (any width) still flows; python lists coerce to int
    assert isolated.union_size(np.array([0, 1], np.uint16)) > 0
    assert isolated.intersection_size((0, 1)) >= 0
    isolated.ingest(np.array([[0, 1]], np.uint16))


# ------------------------------------------------------------ regs staleness
def test_regs_version_bumps_on_donation(graph):
    edges, n = graph
    eng = engine.open(n, CFG)
    assert eng.version == 0
    before = eng.regs
    eng.ingest(edges[:100])
    assert eng.version == 1          # donation happened: old handle is stale
    assert eng.regs is not before    # accessor returns the fresh handle
    eng.ingest(np.zeros((0, 2), np.int32))
    assert eng.version == 1          # no-op block: nothing donated
    other = engine.open(n, CFG).ingest(edges[100:200])
    eng.merge(other)
    assert eng.version == 2
    assert other.version == 1        # merge leaves the other panel alone


# ----------------------------------------------------------- kernel registry
def test_registry_lists_builtin_impls():
    for op in registry.OPS:
        assert {"ref", "pallas"} <= set(registry.impls(op))


def test_registry_lookup_unknown_names_alternatives():
    with pytest.raises(KeyError, match="registered impls.*ref"):
        registry.lookup("accumulate", "cuda")


def test_resolve_unknown_impl_fails_up_front():
    with pytest.raises(ValueError, match="impl"):
        registry.resolve("cuda")
    with pytest.raises(ValueError, match="impl"):
        engine.open(8, CFG, impl="cuda")


def test_resolve_checks_propagate_mask_capability():
    """Bucketed propagate plans pass a mask — impls without one fail."""
    def maskless_op(*a, **k):
        """A complete-looking impl whose propagate cannot take a mask."""
        raise AssertionError("never called")

    def maskless_propagate(regs, src, dst):
        """Propagate missing the mask parameter (the capability gap)."""
        raise AssertionError("never called")

    impl = "test-maskless"
    fam = registry.family("hll")
    for op in fam.ops:
        registry._REGISTRY[(fam.name, op, impl)] = (
            maskless_propagate if op == "propagate" else maskless_op)
    try:
        with pytest.raises(ValueError, match="mask"):
            registry.resolve(impl)
    finally:
        for op in fam.ops:
            registry._REGISTRY.pop((fam.name, op, impl), None)


def test_resolve_records_beta_estimator_fallback(graph):
    """The beta estimator bypasses the fused s/z kernel *explicitly*."""
    edges, n = graph
    cfg = HLLConfig(p=8, estimator="beta")
    ks = registry.resolve("pallas", cfg)
    assert ks.estimate_fallback is not None
    assert "beta" in ks.estimate_fallback
    assert registry.resolve("pallas", CFG).estimate_fallback is None
    # the fallback path serves degrees and matches the jnp reference
    eng = engine.build(edges[:200], n, cfg, backend="local")
    assert eng.kernels.estimate_fallback is not None
    rows = eng.regs
    if eng.layout == "packed":     # the jnp reference speaks byte layout
        from repro.kernels import packing
        rows = packing.unpack_rows(rows)
    expect = np.asarray(hll.estimate(rows, cfg))[:n]
    np.testing.assert_allclose(eng.degrees(), expect, rtol=1e-4)


def test_interpret_mode_resolved_per_call(monkeypatch):
    """Forcing a platform after import must flip interpret mode (satellite:
    the old module-level _INTERPRET froze the backend seen at import)."""
    assert registry.interpret_mode() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert registry.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert registry.interpret_mode() is True


def test_kernel_set_is_hashable_plan_key_material():
    a = registry.resolve("ref", CFG)
    b = registry.resolve("ref", CFG)
    assert a == b and hash(a) == hash(b)
    assert a != registry.resolve("pallas", CFG)


# ------------------------------------------------------------ spans, names
def test_span_nests_counts_and_accumulates():
    plans.reset_span_stats()
    with plans.span("ds.test.outer", step=1) as outer:
        for _ in range(3):
            with plans.span("ds.test.inner"):
                pass
    with plans.span("ds.test.outer"):
        pass
    st = plans.span_stats()
    assert st["ds.test.inner"]["count"] == 3
    assert st["ds.test.outer"]["count"] == 2
    assert outer.seconds > 0
    # the outer span holds its three inner ones; totals add per name
    assert st["ds.test.outer"]["total_ms"] >= outer.seconds * 1e3 \
        >= st["ds.test.inner"]["total_ms"] >= 0
    plans.reset_span_stats()
    assert plans.span_stats() == {}


def test_span_counts_lose_no_update_across_threads():
    import sys
    import threading

    plans.reset_span_stats()
    threads, each = 16, 2000

    def work():
        for _ in range(each):
            with plans.span("ds.test.threads"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    assert plans.span_stats()["ds.test.threads"]["count"] == threads * each
    plans.reset_span_stats()


def test_engine_spans_land_beside_executed_ops_in_a_profile(graph, tmp_path):
    import glob
    import os

    edges, n = graph
    eng = engine.open(n, CFG, backend="local")
    eng.ingest(edges)                       # compile outside the profile
    eng.union_size([np.arange(3)])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.ingest(edges)
        eng.union_size([np.arange(3)])
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    from jax.profiler import ProfileData
    host = [(line.name, ev.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    names = {name for _, name in host}
    assert {"ds.engine.ingest", "ds.engine.query.fetch"} <= names
    # the accumulate's scatter-max ran, on an executor line of the same file
    assert any("scatter" in name for line, name in host if line != "python")


def _lowered_plan(kind):
    """``(plan, example arguments)`` for each plan builder, tiny shapes."""
    kernels = registry.resolve("ref", CFG)
    regs = np.zeros((16, CFG.r), np.uint8)
    rep = np.zeros((8, CFG.r), np.uint8)
    ids, mask = np.zeros((8, 8), np.int32), np.ones((8, 8), bool)
    pairs, pmask = np.zeros((8, 2), np.int32), np.ones((8,), bool)
    route = (np.zeros(8, np.int32), np.zeros(8, np.int32),
             np.ones(8, bool))
    mixed = ("degrees", "union", "intersection")
    table = {
        "degrees": (lambda: plans.build_degrees_plan(CFG, kernels),
                    (regs,)),
        "union": (lambda: plans.build_union_plan(CFG, kernels),
                  (regs, ids, mask)),
        "union_rep": (lambda: plans.build_union_plan(CFG, kernels, True),
                      (regs, rep, ids, mask)),
        "intersection": (lambda: plans.build_intersection_plan(
            CFG, kernels, "ie", 1), (regs, pairs, pmask)),
        "intersection_rep": (lambda: plans.build_intersection_plan(
            CFG, kernels, "ie", 1, replicas=True),
            (regs, rep, pairs, pmask)),
        "mixed": (lambda: plans.build_mixed_plan(CFG, kernels, mixed, "ie",
                                                 1),
                  (regs, ids, mask, pairs, pmask)),
        "mixed_rep": (lambda: plans.build_mixed_plan(
            CFG, kernels, mixed, "ie", 1, replicas=True),
            (regs, rep, ids, mask, pairs, pmask)),
        "replica_gather": (plans.build_replica_gather_plan,
                           (regs, np.zeros(8, np.int32))),
        "merge": (plans.build_merge_plan, (regs, regs)),
        "hip_delta": (lambda: plans.build_hip_delta_plan(
            registry.resolve("ref", family="ads")), (regs, regs)),
        "propagate": (lambda: plans.build_propagate_plan(kernels),
                      (regs,) + route),
    }
    build, args = table[kind]
    return build().lower(*args)


@pytest.mark.parametrize("kind", [
    "degrees", "union", "union_rep", "intersection", "intersection_rep",
    "mixed", "mixed_rep", "replica_gather", "merge", "hip_delta",
    "propagate"])
def test_plan_programs_are_named_by_kind(kind):
    lowered = _lowered_plan(kind)
    text = lowered.as_text()
    assert f"module @jit_plan_{kind} " in text
    assert "jit_fn" not in text
    # ops carry their kernel op's named scope below the program's name
    scope = {"degrees": "estimate_rows", "union": "union_estimate",
             "intersection": "intersection_stats", "propagate": "propagate",
             "hip_delta": "hip_delta"}.get(kind.replace("_rep", ""))
    if scope is not None:
        assert f"jit(plan_{kind})/{scope}/" in lowered.as_text(
            debug_info=True)
