"""Engine snapshots: frozen semantics, lease protocol, cache handoff.

The ISSUE 6 snapshot contract (DESIGN.md §3d):
(a) ``SketchEngine.snapshot()`` is a read-only view frozen at the
    engine's current version — answers are bit-identical to a direct
    engine holding exactly the snapshot's edges, on both backends;
(b) the writer keeps ingesting after a snapshot without ever mutating
    it (the lease protocol clones the register panel before the next
    donating step — rotation never observes a donated panel);
(c) mutating calls on a snapshot raise ``SnapshotFrozen``;
(d) the t-hop panel cache is handed to a same-version snapshot, so a
    snapshot's first ``neighborhood`` query runs ZERO propagate passes.
"""
import numpy as np
import pytest

from repro import engine
from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.engine.base import SnapshotFrozen
from repro.graph import generators as gen
from repro.serve.snapshot import RotationPolicy, SnapshotSlot

CFG = HLLConfig(p=8)
BACKENDS = ["local", "sharded"]


@pytest.fixture(scope="module")
def graph():
    edges = gen.rmat(8, 8, seed=5)
    return edges, int(edges.max()) + 1


def _build(edges, n, backend):
    kw = {"shards": 1} if backend == "sharded" else {}
    return engine.build(edges, n, CFG, backend=backend, **kw)


@pytest.mark.parametrize("backend", BACKENDS)
class TestSnapshotSemantics:
    def test_answers_frozen_at_version(self, graph, backend):
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        snap = eng.snapshot()
        ref = _build(edges[:1000], n, backend)
        # writer moves on; the snapshot must not
        eng.ingest(edges[1000:2000])
        assert np.array_equal(np.asarray(snap.degrees()),
                              np.asarray(ref.degrees()))
        assert np.array_equal(
            np.asarray(snap.union_size([[0, 1, 2], [7, 9]])),
            np.asarray(ref.union_size([[0, 1, 2], [7, 9]])))
        assert np.array_equal(
            np.asarray(snap.intersection_size(edges[:16])),
            np.asarray(ref.intersection_size(edges[:16])))

    def test_writer_correct_after_snapshot(self, graph, backend):
        """The lease clone: writer ingest after snapshot() stays exact."""
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        eng.snapshot()
        eng.ingest(edges[1000:2000])
        ref = _build(edges[:2000], n, backend)
        assert np.array_equal(np.asarray(eng.degrees()),
                              np.asarray(ref.degrees()))

    def test_versions(self, graph, backend):
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        v = eng.version
        snap = eng.snapshot()
        assert snap.version == v and snap.frozen
        eng.ingest(edges[1000:1500])
        assert eng.version > v and snap.version == v
        assert not eng.frozen

    def test_mutations_frozen(self, graph, backend):
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        snap = eng.snapshot()
        with pytest.raises(SnapshotFrozen):
            snap.ingest(edges[1000:1100])
        with pytest.raises(SnapshotFrozen):
            snap.merge(eng)

    def test_edge_list_isolated(self, graph, backend):
        """Writer edge appends never leak into the snapshot's edge list."""
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        snap = eng.snapshot()
        eng.ingest(edges[1000:])
        assert len(snap.edges) == 1000
        assert len(eng.edges) == len(edges)

    def test_panel_cache_handoff(self, graph, backend):
        """A same-version snapshot serves neighborhood() from the donated
        panel cache: zero propagate passes on its first query."""
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        eng.neighborhood(2)  # populate the writer's (version, sched) panels
        snap = eng.snapshot()
        plans.reset_event_counts()
        local, glob = snap.neighborhood(2)
        assert plans.event_counts().get("propagate_pass", 0) == 0
        ref = _build(edges[:1000], n, backend)
        _, glob_ref = ref.neighborhood(2)
        assert np.array_equal(np.asarray(glob), np.asarray(glob_ref))

    def test_snapshot_without_panels_recomputes(self, graph, backend):
        """No cached panels at snapshot time: the snapshot builds its own
        (and the writer's later ingest can't corrupt them)."""
        edges, n = graph
        eng = _build(edges[:1000], n, backend)
        snap = eng.snapshot()
        eng.ingest(edges[1000:2000])
        _, glob = snap.neighborhood(2)
        ref = _build(edges[:1000], n, backend)
        _, glob_ref = ref.neighborhood(2)
        assert np.array_equal(np.asarray(glob), np.asarray(glob_ref))

    def test_repeated_rotation_never_observes_donation(self, graph, backend):
        """Rotating snapshot-then-ingest repeatedly: every snapshot stays
        bit-identical to the reference at its version."""
        edges, n = graph
        bounds = [500, 750, 1000, len(edges)]
        eng = _build(edges[:bounds[0]], n, backend)
        snaps = []
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            snaps.append((eng.snapshot(), lo))
            eng.ingest(edges[lo:hi])
        for snap, cut in snaps:
            ref = _build(edges[:cut], n, backend)
            assert np.array_equal(np.asarray(snap.degrees()),
                                  np.asarray(ref.degrees())), cut


def test_snapshot_extends_its_own_routing(graph):
    """The writer extends its propagate routing after a snapshot; the
    snapshot shares the older routing and extends it from its own edges."""
    edges, n = graph
    eng = _build(edges[:900], n, "local")
    eng.neighborhood(3)
    shared = eng._prop_routing                  # covers 900 edges
    eng.ingest(edges[900:1000])                 # kept, not yet extended
    snap = eng.snapshot()                       # version at 1,000 edges
    eng.ingest(edges[1000:1100])
    w_local, _ = eng.neighborhood(3)            # the writer extends
    assert eng._prop_routing.covered == 1100
    plans.reset_event_counts()
    s_local, s_glob = snap.neighborhood(3)
    assert plans.event_counts().get("routing_extend", 0) == 1
    assert snap._prop_routing.covered == 1000
    assert (shared.covered, shared.filled) == (900, 1800)
    ref_l, ref_g = _build(edges[:1000], n, "local").neighborhood(3)
    np.testing.assert_array_equal(s_local, ref_l)
    np.testing.assert_array_equal(s_glob, ref_g)
    np.testing.assert_array_equal(
        w_local, _build(edges[:1100], n, "local").neighborhood(3)[0])


class TestRotationPolicy:
    def test_validation(self):
        with pytest.raises(ValueError):
            RotationPolicy(every_blocks=0)
        with pytest.raises(ValueError):
            RotationPolicy(max_staleness=0.0)

    def test_due_by_blocks(self):
        pol = RotationPolicy(every_blocks=3)
        assert not pol.due(0, 999.0)
        assert not pol.due(2, 999.0)  # no staleness timer configured
        assert pol.due(3, 0.0)

    def test_due_by_staleness(self):
        pol = RotationPolicy(every_blocks=100, max_staleness=0.5)
        assert not pol.due(1, 0.1)
        assert pol.due(1, 0.5)
        assert not pol.due(0, 99.0)  # nothing pending: never rotate

    def test_timeout(self):
        pol = RotationPolicy(every_blocks=100, max_staleness=1.0)
        assert pol.timeout(0, 0.0) is None
        assert pol.timeout(1, 0.25) == pytest.approx(0.75)
        assert pol.timeout(1, 2.0) == 0.0
        assert RotationPolicy().timeout(1, 5.0) is None


class TestSnapshotSlot:
    def test_swap_and_stats(self, graph):
        edges, n = graph
        eng = _build(edges[:1000], n, "local")
        slot = SnapshotSlot(eng.snapshot())
        assert slot.rotations == 0
        first = slot.get()
        eng.ingest(edges[1000:1500])
        old = slot.swap(eng.snapshot())
        assert old is first and slot.get() is not first
        assert slot.rotations == 1
        st = slot.stats(writer_version=eng.version)
        assert st["version"] == eng.version
        assert st["version_lag"] == 0
        assert st["age_seconds"] >= 0.0
