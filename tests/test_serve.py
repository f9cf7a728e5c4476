"""QueryServer: coalescing, bit-identity, ingest/query epochs.

Acceptance contract (ISSUE 3):
(a) N concurrent mixed-size query clients are served with O(log N)
    compiled programs (asserted via the plan layer's trace counters);
(b) served answers are bit-identical to direct engine calls, on both
    backends — micro-batched rows are computed independently under the
    padding masks, so batch composition cannot leak between requests;
(c) queries interleaved with ingest blocks never crash or observe a
    donated-away register panel (the worker serializes donation against
    reads; the epoch records which panel answered).
"""
import threading

import numpy as np
import pytest

from repro import engine
from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.graph import generators as gen
from repro.serve import QueryServer, ServerClosed

CFG = HLLConfig(p=8)
BACKENDS = ["local", "sharded"]


@pytest.fixture(scope="module")
def graph():
    edges = gen.rmat(8, 8, seed=5)
    return edges, int(edges.max()) + 1


def _open(n, backend):
    return engine.open(n, CFG, backend=backend,
                       shards=1 if backend == "sharded" else None)


def _build(edges, n, backend):
    return _open(n, backend).ingest(edges)


@pytest.mark.parametrize("backend", BACKENDS)
def test_served_answers_bit_identical_to_direct(graph, backend):
    edges, n = graph
    direct = _build(edges, n, backend)
    with QueryServer(_build(edges, n, backend)) as srv:
        np.testing.assert_array_equal(srv.degrees(), direct.degrees())
        sets = [np.array([0, 1, 2]), np.array([n - 1]), np.arange(20)]
        np.testing.assert_array_equal(srv.union_size(sets),
                                      direct.union_size(sets))
        assert srv.union_size(np.array([4, 5])) == \
            direct.union_size(np.array([4, 5]))  # scalar form
        pairs = edges[:13]
        np.testing.assert_array_equal(srv.intersection_size(pairs),
                                      direct.intersection_size(pairs))
        t_s = srv.triangle_heavy_hitters(k=5)
        t_d = direct.triangle_heavy_hitters(k=5)
        assert t_s[0] == t_d[0]
        np.testing.assert_array_equal(t_s[1], t_d[1])
        np.testing.assert_array_equal(t_s[2], t_d[2])


def test_coalesced_batch_bit_identical_per_request(graph):
    """Requests fused into one micro-batch answer exactly like solo calls."""
    edges, n = graph
    direct = _build(edges, n, "local")
    with QueryServer(_build(edges, n, "local")) as srv:
        srv.pause()
        sets_a = [np.arange(5), np.array([n - 1])]
        sets_b = [np.arange(30)]  # different length -> shared padding bucket
        ra = srv._submit("union", plans.split_sets(sets_a, n))
        rb = srv._submit("union", plans.split_sets(sets_b, n))
        pa = edges[:3].astype(np.int64)
        pb = edges[3:20].astype(np.int64)
        ia = srv._submit("intersection", (pa, False, "mle", 50))
        ib = srv._submit("intersection", (pb, False, "mle", 50))
        srv.resume()
        np.testing.assert_array_equal(ra.wait(), direct.union_size(sets_a))
        np.testing.assert_array_equal(rb.wait(), direct.union_size(sets_b))
        np.testing.assert_array_equal(ia.wait(),
                                      direct.intersection_size(pa))
        np.testing.assert_array_equal(ib.wait(),
                                      direct.intersection_size(pb))
        stats = srv.stats()
    assert stats["union"]["batches"] == 1       # 2 requests, 1 engine call
    assert stats["union"]["max_coalesced"] == 2
    assert stats["intersection"]["batches"] == 1


def test_concurrent_mixed_clients_log_bound_programs(graph):
    """The acceptance bound: N clients, jittering batches, O(log N) programs."""
    edges, n = graph
    eng = _build(edges, n, "local")
    eng._plan_cache = plans.PlanCache(maxsize=64)  # isolate compile counting
    plans.reset_trace_counts()
    n_clients, per_client = 8, 6
    errors: list = []
    direct = _build(edges, n, "local")

    def client(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(per_client):
                size = int(rng.integers(1, 33))  # jittering batch sizes
                idx = rng.integers(0, len(edges), size=size)
                got = srv.intersection_size(edges[idx])
                np.testing.assert_array_equal(
                    got, direct.intersection_size(edges[idx]))
                sets = [rng.integers(0, n, size=3) for _ in range(size)]
                np.testing.assert_array_equal(srv.union_size(sets),
                                              direct.union_size(sets))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    with QueryServer(eng) as srv:
        threads = [threading.Thread(target=client, args=(100 + i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.stats()
    assert not errors, errors
    traces = plans.trace_counts()
    # worst case coalesced batch: 8 clients * 32 rows = 256 -> buckets
    # {8..256}: 6 programs. The bound is O(log(N * max_batch)).
    bound = int(np.log2(n_clients * 32)) + 2
    assert traces["intersection"] <= bound, traces
    assert traces["union"] <= bound, traces
    assert stats["requests_total"] == n_clients * per_client * 2


def test_mixed_kind_segment_fused_into_one_program(graph):
    """Coalesced degrees+union+intersection ride ONE mixed program and
    stay bit-identical to direct per-kind engine calls (ISSUE 5)."""
    edges, n = graph
    direct = _build(edges, n, "local")
    eng = _build(edges, n, "local")
    eng._plan_cache = plans.PlanCache(maxsize=32)
    sets = [np.arange(5), np.array([n - 1])]
    pa = edges[:6].astype(np.int64)
    want_u = direct.union_size(sets)
    want_d = direct.degrees()
    want_i = direct.intersection_size(pa)
    with QueryServer(eng) as srv:
        srv.pause()
        ru = srv._submit("union", plans.split_sets(sets, n))
        rd = srv._submit("degrees", ())
        ri = srv._submit("intersection", (pa, False, "mle", 50))
        plans.reset_trace_counts()
        srv.resume()
        np.testing.assert_array_equal(ru.wait(), want_u)
        np.testing.assert_array_equal(rd.wait(), want_d)
        np.testing.assert_array_equal(ri.wait(), want_i)
        traces = plans.trace_counts()
        stats = srv.stats()
    assert traces == {"mixed": 1}, traces  # one program for three kinds
    assert stats["fused_batches"] == 1
    for kind in ("union", "degrees", "intersection"):
        assert stats[kind]["batches"] == 1


def test_mixed_segment_extra_intersection_group_served_unfused(graph):
    """A second (method, iters) group can't share the fused program — it
    is served through the per-kind plan in the same drain, correctly."""
    edges, n = graph
    direct = _build(edges, n, "local")
    with QueryServer(_build(edges, n, "local")) as srv:
        srv.pause()
        rd = srv._submit("degrees", ())
        pa = edges[:3].astype(np.int64)
        pb = edges[3:8].astype(np.int64)
        ra = srv._submit("intersection", (pa, False, "mle", 50))
        rb = srv._submit("intersection", (pb, False, "ie", 50))
        srv.resume()
        np.testing.assert_array_equal(rd.wait(), direct.degrees())
        np.testing.assert_array_equal(ra.wait(),
                                      direct.intersection_size(pa))
        np.testing.assert_array_equal(
            rb.wait(), direct.intersection_size(pb, method="ie"))


def test_reset_stats_clears_the_window(graph):
    edges, n = graph
    with QueryServer(_build(edges, n, "local")) as srv:
        srv.degrees()
        assert srv.stats()["requests_total"] == 1
        srv.reset_stats()
        stats = srv.stats()
        assert stats["requests_total"] == 0
        assert stats["fused_batches"] == 0
        assert stats["plan_traces"] == {}  # trace baseline re-anchored
        srv.degrees()  # the server keeps serving after a reset
        assert srv.stats()["requests_total"] == 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_queries_interleaved_with_ingest(graph, backend):
    """Clients query while blocks stream in: no crash, no stale panel."""
    edges, n = graph
    srv_eng = _open(n, backend)
    srv_eng.ingest(edges[: len(edges) // 4])
    full = _build(edges, n, backend)
    errors: list = []
    stop = threading.Event()

    def client(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                srv.degrees()
                idx = rng.integers(0, len(edges), size=int(rng.integers(1, 9)))
                srv.intersection_size(edges[idx])
                srv.union_size([rng.integers(0, n, size=4)])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    with QueryServer(srv_eng) as srv:
        threads = [threading.Thread(target=client, args=(7 + i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        rest = edges[len(edges) // 4:]
        step = max(1, len(rest) // 6)
        for s in range(0, len(rest), step):  # live ingest under query load
            srv.ingest(rest[s:s + step])
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors
        assert srv.epoch >= 6
        # after the last barrier the server answers like the full build
        np.testing.assert_array_equal(srv.degrees(), full.degrees())


@pytest.mark.parametrize("backend", BACKENDS)
def test_served_neighborhood_bit_identical_to_direct(graph, backend):
    edges, n = graph
    direct = _build(edges, n, backend)
    l_d, g_d = direct.neighborhood(3)
    with QueryServer(_build(edges, n, backend)) as srv:
        l_s, g_s = srv.neighborhood(3)
        np.testing.assert_array_equal(l_s, l_d)
        np.testing.assert_array_equal(g_s, g_d)
        # repeat rides the cached panels and stays bit-identical
        l_s2, g_s2 = srv.neighborhood(3)
        np.testing.assert_array_equal(l_s2, l_d)
        np.testing.assert_array_equal(g_s2, g_d)


def test_served_neighborhood_coalesces_per_schedule(graph):
    """Concurrent horizons dedupe into ONE engine call at the deepest t."""
    edges, n = graph
    direct = _build(edges, n, "local")
    l_d, g_d = direct.neighborhood(3)
    with QueryServer(_build(edges, n, "local")) as srv:
        srv.pause()
        key = srv.engine._canonical_schedule("auto")
        r2 = srv._submit("neighborhood", (2, "auto", key))
        r3 = srv._submit("neighborhood", (3, "ring", key))  # same key
        srv.resume()
        l2, g2 = r2.wait()
        l3, g3 = r3.wait()
        np.testing.assert_array_equal(l3, l_d)
        np.testing.assert_array_equal(g3, g_d)
        np.testing.assert_array_equal(l2, l_d[:2])  # the t-prefix
        np.testing.assert_array_equal(g2, g_d[:2])
        stats = srv.stats()
    assert stats["neighborhood"]["requests"] == 2
    assert stats["neighborhood"]["batches"] == 1   # ONE engine call
    assert stats["neighborhood"]["max_coalesced"] == 2


def test_served_neighborhood_panel_cache_hit_asserted(graph):
    """Second served query: zero propagate passes, no propagate retrace."""
    edges, n = graph
    eng = _build(edges, n, "local")
    eng._plan_cache = plans.PlanCache(maxsize=32)
    with QueryServer(eng) as srv:
        srv.neighborhood(3)
        plans.reset_trace_counts()
        plans.reset_event_counts()
        srv.neighborhood(3)
        assert plans.event_counts().get("propagate_pass", 0) == 0
        assert "propagate" not in plans.trace_counts()


@pytest.mark.parametrize("backend", BACKENDS)
def test_served_neighborhood_ingest_invalidates(graph, backend):
    """An ingest barrier between queries: the later answer is the new
    epoch's (panel cache invalidated by the version bump)."""
    edges, n = graph
    half = len(edges) // 2
    full_l, _ = _build(edges, n, backend).neighborhood(2)
    with QueryServer(_build(edges[:half], n, backend)) as srv:
        before_l, _ = srv.neighborhood(2)
        epoch = srv.ingest(edges[half:])
        after_l, _ = srv.neighborhood(2)
        assert epoch == 1
        np.testing.assert_array_equal(after_l, full_l)
        assert not np.array_equal(before_l, after_l)


def test_served_neighborhood_validates_on_client_thread(graph):
    edges, n = graph
    with QueryServer(_build(edges, n, "local")) as srv:
        with pytest.raises(ValueError, match="t_max"):
            srv.neighborhood(0)
        with pytest.raises(ValueError, match="schedule"):
            srv.neighborhood(2, schedule="nope")
        # an edge-free engine fails the request worker-side, others live
        l, g = srv.neighborhood(2)
        assert l.shape == (2, n) and g.shape == (2,)


def test_epoch_barrier_orders_reads(graph):
    """Queries before/after an ingest barrier see exactly that panel."""
    edges, n = graph
    half = len(edges) // 2
    half_eng = _build(edges[:half], n, "local")
    full_eng = _build(edges, n, "local")
    with QueryServer(_build(edges[:half], n, "local")) as srv:
        srv.pause()
        before = srv._submit("degrees", ())
        barrier = srv._submit("ingest", (edges[half:],))
        after = srv._submit("degrees", ())
        srv.resume()
        np.testing.assert_array_equal(before.wait(), half_eng.degrees())
        assert barrier.wait() == 1
        np.testing.assert_array_equal(after.wait(), full_eng.degrees())
    assert before.epoch == 0 and after.epoch == 1


def test_request_errors_propagate_to_caller_only(graph):
    edges, n = graph
    with QueryServer(_build(edges, n, "local")) as srv:
        with pytest.raises(ValueError, match="universe"):
            srv.union_size([np.array([n + 5])])     # client-side validation
        with pytest.raises(ValueError, match="universe"):
            srv.ingest(np.array([[0, n]]))          # worker-side validation
        with pytest.raises(ValueError, match="method"):
            srv.intersection_size(edges[:2], method="nope")
        # the server keeps serving afterwards
        assert srv.degrees().shape == (n,)


def test_worker_side_error_does_not_poison_batch(graph):
    """An edge-free engine fails triangle requests but serves the rest."""
    edges, n = graph
    built = _build(edges, n, "local")
    bare = engine.LocalEngine.from_regs(
        np.asarray(built.regs)[:n], n, CFG,  # no edges -> no replay queries
        layout=built.layout)
    with QueryServer(bare) as srv:
        srv.pause()
        tri = srv._submit("triangle", (5, "edge", 30))
        deg = srv._submit("degrees", ())
        srv.resume()
        with pytest.raises(ValueError, match="edge stream"):
            tri.wait()
        np.testing.assert_array_equal(deg.wait(), built.degrees())


def test_closed_server_rejects_requests(graph):
    edges, n = graph
    srv = QueryServer(_build(edges[:50], n, "local"))
    assert srv.degrees().shape == (n,)
    srv.close()
    with pytest.raises(ServerClosed):
        srv.degrees()
    srv.close()  # idempotent


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_worker_crash_fails_pending_with_server_closed(graph):
    """A dying worker (BaseException) never leaves a future hanging:
    the in-flight batch fails, the backlog fails with ServerClosed, and
    later submits are rejected (ISSUE 6 satellite: shutdown contract)."""
    edges, n = graph
    srv = QueryServer(_build(edges[:200], n, "local"))
    try:
        srv.pause()
        r1 = srv._submit("degrees", ())
        r2 = srv._submit("union", ([np.array([0, 1])], False))

        def boom(batch):
            raise SystemExit("worker crash")
        srv._serve = boom
        srv.resume()
        for r in (r1, r2):
            with pytest.raises(BaseException):
                r.wait()
        srv._worker.join(timeout=30)
        assert srv._dead
        with pytest.raises(ServerClosed):
            srv.degrees()
    finally:
        srv.close()  # close after a crash is safe and idempotent


def test_shutdown_alias_and_stats_schema(graph):
    """shutdown() == close(); stats() carries the serving-frontend schema
    (queue depth, p999, histograms, shed/deadline counters)."""
    edges, n = graph
    srv = QueryServer(_build(edges[:200], n, "local"))
    srv.degrees()
    srv.union_size([[0, 1, 2]])
    st = srv.stats()
    for key in ("epoch", "queue_depth", "requests_total", "fused_batches",
                "shed_total", "deadline_misses", "plan_traces",
                "plan_cache", "runtime"):
        assert key in st, key
    assert st["queue_depth"] == 0
    assert st["shed_total"] == 0 and st["deadline_misses"] == 0
    for key in ("heartbeats_seen", "evictions", "recoveries",
                "last_recovery_ms", "checkpoints_written"):
        assert key in st["runtime"], key
    assert st["runtime"]["heartbeats_seen"] >= 1  # worker drained queries
    assert st["runtime"]["evictions"] == 0  # no failover writer here
    for kind in ("degrees", "union"):
        s = st[kind]
        for key in ("requests", "batches", "max_coalesced", "p50_ms",
                    "p99_ms", "p999_ms", "histogram_ms"):
            assert key in s, (kind, key)
        assert sum(c for _, c in s["histogram_ms"]) == s["requests"]
        assert all(c > 0 for _, c in s["histogram_ms"])
    srv.shutdown()
    with pytest.raises(ServerClosed):
        srv.degrees()
    srv.shutdown()  # idempotent


def test_queue_depth_reported_while_paused(graph):
    edges, n = graph
    with QueryServer(_build(edges[:200], n, "local")) as srv:
        srv.pause()
        a = srv._submit("degrees", ())
        b = srv._submit("degrees", ())
        assert srv.stats()["queue_depth"] == 2
        srv.resume()
        a.wait()
        b.wait()
        assert srv.stats()["queue_depth"] == 0


def _traffic(srv, edges, n):
    """5 union and 3 intersection requests around two ingest barriers."""
    for i in range(5):
        srv.union_size([np.arange(i + 1), np.array([n - 1])])
    srv.ingest(edges[:50])
    for i in range(3):
        srv.intersection_size(edges[i:i + 4])
    srv.ingest(edges[50:90])
    return 8


def test_queue_wait_counts_query_requests_only(graph):
    edges, n = graph
    srv = QueryServer(_open(n, "local"))
    srv.reset_stats()
    queries = _traffic(srv, edges, n)
    srv.close()
    qw = srv.stats()["queue_wait_ms"]
    assert qw["count"] == queries            # the two ingests excluded
    assert 0 <= qw["p50"] <= qw["p95"] <= qw["p99"]


def test_worker_time_and_drain_spans(graph):
    edges, n = graph
    srv = QueryServer(_open(n, "local"))
    srv.reset_stats()
    drains0 = srv.stats()["runtime"]["heartbeats_seen"]
    _traffic(srv, edges, n)
    srv.close()                  # every drain span has closed
    st = srv.stats()
    ws = st["worker_s"]
    assert set(ws) == {"window", "wait", "ingest", "query", "account"}
    assert all(v >= 0 for v in ws.values())
    assert ws["wait"] + ws["ingest"] + ws["query"] + ws["account"] \
        <= ws["window"]
    assert ws["ingest"] > 0 and ws["query"] > 0 and ws["wait"] > 0
    spans = st["spans"]
    drains = st["runtime"]["heartbeats_seen"] - drains0
    assert spans["ds.serve.drain"]["count"] == drains
    assert spans["ds.serve.ingest"]["count"] == 2
    assert spans["ds.engine.ingest"]["count"] == 2
    assert spans["ds.serve.segment"]["count"] \
        == spans["ds.serve.account"]["count"] >= 1
    assert spans["ds.serve.segment"]["total_ms"] \
        >= spans["ds.engine.query.fetch"]["total_ms"] > 0


def test_reset_stats_zeroes_host_time(graph):
    edges, n = graph
    srv = QueryServer(_open(n, "local"))
    _traffic(srv, edges, n)
    srv.close()
    before = srv.stats()
    assert before["queue_wait_ms"]["count"] and before["spans"]
    srv.reset_stats()
    st = srv.stats()
    assert st["queue_wait_ms"] == {"p50": None, "p95": None, "p99": None,
                                   "count": 0}
    assert st["worker_s"]["window"] < before["worker_s"]["window"]
    assert all(st["worker_s"][k] == 0.0
               for k in ("wait", "ingest", "query", "account"))
    assert st["spans"] == {}
