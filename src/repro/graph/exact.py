"""Exact ground-truth oracles: t-neighborhoods (BFS) and triangle counts.

Used by tests and by the paper-figure benchmarks (MRE, precision/recall).
numpy implementations; fine for the moderate graphs the accuracy
experiments use (the paper's accuracy figures also use moderate graphs).
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "degrees", "adjacency_lists", "neighborhood_truth", "exact_edge_triangles",
    "exact_vertex_triangles", "exact_global_triangles", "kron_edge_triangles",
]


def degrees(n: int, edges: np.ndarray) -> np.ndarray:
    """Exact degree |N(x)| of every vertex of a canonical edge list."""
    return (np.bincount(edges[:, 0], minlength=n)
            + np.bincount(edges[:, 1], minlength=n))


def adjacency_lists(n: int, edges: np.ndarray) -> list[np.ndarray]:
    """Sorted adjacency arrays per vertex from a canonical edge list."""
    deg = np.zeros(n, dtype=np.int64)
    np.add.at(deg, edges[:, 0], 1)
    np.add.at(deg, edges[:, 1], 1)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=offs[1:])
    flat = np.zeros(offs[-1], dtype=np.int32)
    cur = offs[:-1].copy()
    for u, v in edges:
        flat[cur[u]] = v
        cur[u] += 1
        flat[cur[v]] = u
        cur[v] += 1
    return [np.sort(flat[offs[i]:offs[i + 1]]) for i in range(n)]


def neighborhood_truth(n: int, edges: np.ndarray, t_max: int) -> np.ndarray:
    """Ground truth matching Algorithm 2's accumulation semantics.

    Returns int64[t_max, n]. The accumulated sketch D^t[x] contains
    {y != x : d(x,y) <= t}, plus x itself from t >= 2 onward (x enters via
    its neighbors' adjacency sets on the second pass; see line 23's
    D^t <- D^{t-1} copy). Row t-1 holds that target count for pass t.
    """
    adj = adjacency_lists(n, edges)
    out = np.zeros((t_max, n), dtype=np.int64)
    for x in range(n):
        dist = np.full(n, -1, dtype=np.int64)
        dist[x] = 0
        frontier = [x]
        d = 0
        while frontier and d < t_max:
            d += 1
            nxt = []
            for u in frontier:
                for v in adj[u]:
                    if dist[v] < 0:
                        dist[v] = d
                        nxt.append(v)
            frontier = nxt
        reach = dist[dist > 0]
        has_nbr = len(adj[x]) > 0
        for t in range(1, t_max + 1):
            cnt = int(np.sum((reach <= t)))
            # self joins at t>=2, but only via a neighbor's adjacency set
            out[t - 1, x] = cnt + (1 if (t >= 2 and has_nbr) else 0)
    return out


def exact_edge_triangles(n: int, edges: np.ndarray) -> np.ndarray:
    """T(xy) = |N(x) ∩ N(y)| per edge (Eq. 3), via sorted-set intersection."""
    adj = adjacency_lists(n, edges)
    out = np.zeros(len(edges), dtype=np.int64)
    for i, (u, v) in enumerate(edges):
        out[i] = len(np.intersect1d(adj[u], adj[v], assume_unique=True))
    return out


def exact_vertex_triangles(n: int, edges: np.ndarray,
                           edge_tri: np.ndarray | None = None) -> np.ndarray:
    """T(x) = 1/2 sum over incident edges of T(xy) (Eq. 5)."""
    if edge_tri is None:
        edge_tri = exact_edge_triangles(n, edges)
    out = np.zeros(n, dtype=np.int64)
    np.add.at(out, edges[:, 0], edge_tri)
    np.add.at(out, edges[:, 1], edge_tri)
    return out // 2


def exact_global_triangles(n: int, edges: np.ndarray,
                           edge_tri: np.ndarray | None = None) -> int:
    """T = 1/3 sum over edges of T(xy) (Eq. 6)."""
    if edge_tri is None:
        edge_tri = exact_edge_triangles(n, edges)
    return int(edge_tri.sum()) // 3


def kron_edge_triangles(factor_edges: np.ndarray, n_f: int,
                        kron_edges_arr: np.ndarray) -> np.ndarray:
    """Kronecker formula (Sanders et al. 2018): for C = A ⊗ A and a C-edge
    ((u1,u2),(v1,v2)), T_C(e) = (A^2)[u1,v1] * (A^2)[u2,v2] — the
    common-neighbor walks factorize over the product. O(m) total.
    """
    A = np.zeros((n_f, n_f), dtype=np.int64)
    A[factor_edges[:, 0], factor_edges[:, 1]] = 1
    A[factor_edges[:, 1], factor_edges[:, 0]] = 1
    A2 = A @ A
    u1, u2 = kron_edges_arr[:, 0] // n_f, kron_edges_arr[:, 0] % n_f
    v1, v2 = kron_edges_arr[:, 1] // n_f, kron_edges_arr[:, 1] % n_f
    return A2[u1, v1] * A2[u2, v2]
