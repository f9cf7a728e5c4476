import numpy as np
import pytest

from repro.graph import exact, generators as gen
from repro.graph.stream import EdgeStream, bucket_by_owner, owner_of


def test_canonical_undirected():
    e = np.array([[1, 2], [2, 1], [3, 3], [1, 2], [5, 4]])
    out = gen.canonical_undirected(e)
    np.testing.assert_array_equal(out, [[1, 2], [4, 5]])


def test_rmat_shapes_and_powerlaw():
    e = gen.rmat(10, 8, seed=0)
    n = int(e.max()) + 1
    assert n <= 1024
    deg = np.zeros(n)
    np.add.at(deg, e[:, 0], 1)
    np.add.at(deg, e[:, 1], 1)
    # power-law-ish: max degree far above mean
    assert deg.max() > 5 * deg.mean()


def test_exact_degrees_match_adjacency():
    e = gen.rmat(8, 8, seed=3)
    n = 1 << 8
    adj = exact.adjacency_lists(n, e)
    np.testing.assert_array_equal(exact.degrees(n, e),
                                  [len(a) for a in adj])


def test_kronecker_triangle_formula_matches_exact():
    f, nf = gen.named_factor("wheel16")
    ke = gen.kronecker_edges(f, nf, f, nf)
    n = nf * nf
    formula = exact.kron_edge_triangles(f, nf, ke)
    direct = exact.exact_edge_triangles(n, ke)
    np.testing.assert_array_equal(formula, direct)


def test_neighborhood_truth_path_graph():
    # path 0-1-2-3
    edges = np.array([[0, 1], [1, 2], [2, 3]], np.int32)
    truth = exact.neighborhood_truth(4, edges, 3)
    # t=1: degrees
    np.testing.assert_array_equal(truth[0], [1, 2, 2, 1])
    # t=2: reach<=2 minus self plus self(joins at t>=2)
    np.testing.assert_array_equal(truth[1], [3, 4, 4, 3])
    np.testing.assert_array_equal(truth[2], [4, 4, 4, 4])


def test_exact_triangles_clique():
    n = 5
    edges = gen.canonical_undirected(
        np.array([(i, j) for i in range(n) for j in range(i + 1, n)]))
    tri = exact.exact_edge_triangles(n, edges)
    np.testing.assert_array_equal(tri, np.full(len(edges), n - 2))
    assert exact.exact_global_triangles(n, edges, tri) == 10  # C(5,3)
    np.testing.assert_array_equal(
        exact.exact_vertex_triangles(n, edges, tri), np.full(n, 6))  # C(4,2)


def test_stream_partition_covers_all_edges():
    e = gen.erdos_renyi(100, 300, seed=1)
    stream = EdgeStream(e, num_substreams=4, block=32)
    got = np.concatenate([stream.substream(i) for i in range(4)])
    assert len(got) == len(e)
    blocks = list(stream.blocks(0))
    total = sum(int(m.sum()) for _, m in blocks)
    assert total == len(stream.substream(0))


def test_bucket_by_owner_routes_both_directions():
    e = np.array([[0, 9], [5, 3]], np.int32)
    buckets = bucket_by_owner(e, n_pad=16, num_shards=4)
    allp = np.concatenate([b for b in buckets if len(b)])
    assert len(allp) == 4  # both orientations of both edges
    for dst, _ in allp:
        assert 0 <= dst < 16
    np.testing.assert_array_equal(owner_of(np.array([0, 5, 9, 15]), 16, 4),
                                  [0, 1, 2, 3])
