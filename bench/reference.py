"""Plain reference of the sketch service's semantics, for the comparison.

Written from the paper (DegreeSketch, Algorithms 1 and 2; Ertl 2017 for
the intersection estimator) and from the hash the configuration names,
without importing anything of the program under test:

* The hash. A vertex id ``k`` (uint32) maps to a 64-bit word held as two
  uint32 lanes: ``hi = f(k ^ s_hi)``, ``lo = f((k + 0x85EBCA6B) ^ s_lo)``,
  then ``hi = f(hi + lo * 0x9E3779B9)``, where ``f`` is the murmur3
  32-bit finalizer and ``s_hi = seed * 0x9E3779B9 + 0x27D4EB2F``,
  ``s_lo = seed * 0x85EBCA6B + 0x165667B1`` (mod 2**32). The register is
  the top ``p`` bits of ``hi``; ``rho`` is one plus the number of leading
  zeros of the ``64 - p`` bits that follow, capped at ``65 - p``.
* The register table (Algorithm 1). Row ``x`` holds, per register, the
  largest ``rho`` over the neighbours ``y`` of ``x`` whose hash selects
  that register; 0 where none does. Tables are built on the device by a
  plain scatter-max over fixed-size blocks of directed edges.
* Propagation (Algorithm 2). ``D^{t+1}[x] = max(D^t[x], max over the
  neighbours y of D^t[y])``, again a plain blocked gather and scatter-max.
* Estimates, on the host in float64. One row (or the register-wise max
  of a set of rows): ``s = sum 2**-reg``, ``z`` = empty registers, the
  harmonic estimate ``alpha_r r^2 / s`` with ``alpha_r = 0.7213 / (1 +
  1.079 / r)``, replaced by linear counting ``r ln(r / z)`` where the
  harmonic estimate is at most ``2.5 r`` and ``z > 0``. A pair's
  intersection is the maximum-likelihood estimate of Ertl's Poisson
  model, found by Newton's method in float64 from two starts (the
  clipped inclusion-exclusion start and an intersection of 1), the one
  of higher likelihood kept.

The comparison and its control live in ``bench/compare.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "bucket_rho", "Incidence", "table", "add_edges", "propagate", "row_stats",
    "estimate_from_stats", "estimate_rows", "union_estimates",
    "pair_stats", "intersection_mle",
]

_U32 = 0xFFFFFFFF
#: directed edges per scatter-max call of the reference
EDGE_BLOCK = 1 << 20
#: directed edges per gather of the reference propagate
PROPAGATE_BLOCK = 1 << 21


def _fmix32(x):
    """murmur3's 32-bit finalizer on uint32 lanes."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def bucket_rho(keys: jax.Array, p: int, seed: int) -> tuple:
    """(register int32, rho int32) of each uint32 key, as documented above."""
    s_hi = np.uint32((seed * 0x9E3779B9 + 0x27D4EB2F) & _U32)
    s_lo = np.uint32((seed * 0x85EBCA6B + 0x165667B1) & _U32)
    k = keys.astype(jnp.uint32)
    lo = _fmix32((k + np.uint32(0x85EBCA6B)) ^ s_lo)
    hi = _fmix32(_fmix32(k ^ s_hi) + lo * np.uint32(0x9E3779B9))
    reg = (hi >> np.uint32(32 - p)).astype(jnp.int32)
    top = (hi << np.uint32(p)) | (lo >> np.uint32(32 - p))
    low = lo << np.uint32(p)
    zeros = jnp.where(top != 0, jax.lax.clz(top),
                      np.uint32(32) + jax.lax.clz(low)).astype(jnp.int32)
    return reg, jnp.minimum(zeros, 64 - p) + 1


@partial(jax.jit, static_argnames=("p", "seed", "cap"), donate_argnums=(0,))
def _scatter(tab, rows, keys, valid, *, p: int, seed: int, cap: int):
    reg, rho = bucket_rho(keys, p, seed)
    rho = jnp.where(valid, jnp.minimum(rho, cap), 0).astype(jnp.uint8)
    return tab.at[rows, reg].max(rho)


class Incidence:
    """The directed edges into a set of vertices, hashed once, for rows.

    ``edges`` is the whole stream in order; ``block_of[i]`` is -1 for an
    edge present from the start and ``k >= 0`` for one that arrives with
    the ``k``-th block. :meth:`rows` gives the register rows of some of
    the vertices with the blocks ``< blocks`` present, on the host.
    """

    def __init__(self, edges: np.ndarray, block_of: np.ndarray,
                 vertices: np.ndarray, n: int, p: int, seed: int):
        self.vertices = np.unique(np.asarray(vertices, np.int64))
        self.r = 1 << p
        flag = np.zeros(n, bool)
        flag[self.vertices] = True
        parts = []
        for a, b in ((0, 1), (1, 0)):
            idx = np.flatnonzero(flag[edges[:, a]])
            parts.append((edges[idx, a], edges[idx, b], block_of[idx]))
        x = np.concatenate([q[0] for q in parts]).astype(np.int64)
        y = np.concatenate([q[1] for q in parts]).astype(np.int64)
        blk = np.concatenate([q[2] for q in parts])
        order = np.argsort(x, kind="stable")
        self.x, y, self.blk = x[order], y[order], blk[order]
        reg, rho = jax.jit(bucket_rho, static_argnums=(1, 2))(
            jnp.asarray(y.astype(np.uint32)), p, seed)
        self.reg = np.asarray(reg)
        self.rho = np.asarray(rho).astype(np.uint8)
        self.start = np.searchsorted(self.x, self.vertices, side="left")
        self.stop = np.searchsorted(self.x, self.vertices, side="right")

    def rows(self, vertices, blocks: int) -> np.ndarray:
        """Register rows ``uint8[len(vertices), r]`` with blocks < ``blocks``."""
        vertices = np.asarray(vertices, np.int64).ravel()
        k = np.searchsorted(self.vertices, vertices)
        out = np.zeros((len(vertices), self.r), np.uint8)
        for i, j in enumerate(k):
            sl = slice(self.start[j], self.stop[j])
            keep = self.blk[sl] < blocks
            np.maximum.at(out[i], self.reg[sl][keep], self.rho[sl][keep])
        return out


def table(n: int, p: int) -> jax.Array:
    """An empty register table ``uint8[n, 2**p]`` on the device."""
    return jnp.zeros((n, 1 << p), jnp.uint8)


def add_edges(tab: jax.Array, edges: np.ndarray, p: int, seed: int,
              cap: int = 255) -> jax.Array:
    """Fold undirected ``edges`` (host ``int[m, 2]``) into ``tab``.

    Both orientations are inserted. ``cap`` bounds each register (the
    control passes 15). Returns the new table; ``tab`` is donated.
    """
    edges = np.asarray(edges)
    directed = np.concatenate([edges, edges[:, ::-1]]).astype(np.int32)
    block = min(EDGE_BLOCK, 1 << max(len(directed) - 1, 0).bit_length())
    for s in range(0, len(directed), block):
        part = directed[s:s + block]
        rows = np.zeros(block, np.int32)
        keys = np.zeros(block, np.int32)
        valid = np.zeros(block, bool)
        rows[:len(part)], keys[:len(part)] = part[:, 0], part[:, 1]
        valid[:len(part)] = True
        tab = _scatter(tab, rows, keys.view(np.uint32), valid, p=p,
                       seed=seed, cap=cap)
    return tab


@partial(jax.jit, donate_argnums=(1,))
def _gather_max(src_tab, out, src, dst, valid):
    rows = jnp.where(valid[:, None], src_tab[src], jnp.uint8(0))
    return out.at[dst].max(rows)


def propagate(tab: jax.Array, edges: np.ndarray) -> jax.Array:
    """One pass of Algorithm 2 over undirected ``edges``; ``tab`` is kept."""
    edges = np.asarray(edges)
    directed = np.concatenate([edges, edges[:, ::-1]]).astype(np.int32)
    out = jnp.copy(tab)
    for s in range(0, len(directed), PROPAGATE_BLOCK):
        part = directed[s:s + PROPAGATE_BLOCK]
        src = np.zeros(PROPAGATE_BLOCK, np.int32)
        dst = np.zeros(PROPAGATE_BLOCK, np.int32)
        valid = np.zeros(PROPAGATE_BLOCK, bool)
        src[:len(part)], dst[:len(part)] = part[:, 0], part[:, 1]
        valid[:len(part)] = True
        out = _gather_max(tab, out, src, dst, valid)
    return out


# ------------------------------------------------------------- estimates
def _alpha(r: int) -> float:
    return 0.7213 / (1.0 + 1.079 / r)


def row_stats(rows: np.ndarray, dtype=np.float64) -> tuple:
    """(s, z) of register rows ``uint8[..., r]`` in ``dtype``."""
    rows = np.asarray(rows)
    s = np.exp2(-rows.astype(np.float64)).astype(dtype).sum(
        axis=-1, dtype=dtype)
    z = (rows == 0).sum(axis=-1).astype(dtype)
    return s, z


def estimate_from_stats(s, z, r: int, dtype=np.float64) -> tuple:
    """(estimate, harmonic, linear) from ``(s, z)``, computed in ``dtype``.

    The harmonic and linear estimates are returned too, so that a
    comparison can accept either branch where rounding decides it.
    """
    s = np.asarray(s, dtype)
    z = np.asarray(z, dtype)
    rr = dtype(r)
    raw = (dtype(_alpha(r)) * rr * rr / s).astype(dtype)
    lin = (rr * np.log(rr / np.maximum(z, dtype(1)))).astype(dtype)
    use_lin = (raw <= dtype(2.5) * rr) & (z > 0)
    return (np.where(use_lin, lin, raw).astype(np.float64),
            raw.astype(np.float64), lin.astype(np.float64))


def estimate_rows(rows: np.ndarray, r: int, dtype=np.float64) -> tuple:
    """Per-row estimates of ``uint8[N, r]`` rows: (estimate, raw, lin)."""
    s, z = row_stats(rows, dtype)
    return estimate_from_stats(s, z, r, dtype)


def union_estimates(rows_of_sets: list, r: int, dtype=np.float64) -> tuple:
    """Estimates of the register-wise max of each list of rows."""
    merged = np.stack([np.max(np.asarray(rows), axis=0)
                       for rows in rows_of_sets])
    return estimate_rows(merged, r, dtype)


# ------------------------------------------------ intersection estimator
def pair_stats(a: np.ndarray, b: np.ndarray, q: int) -> np.ndarray:
    """Register-value histograms of pairs of rows ``uint8[P, r]``.

    Returns ``float64[P, 5, q + 2]``: counts of register values ``k`` of
    A where A < B, of B where B > A, of A where A > B, of B where B < A,
    and of A where A = B.
    """
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    out = np.zeros((a.shape[0], 5, q + 2))
    idx = np.arange(a.shape[0])[:, None]
    for i, (vals, sel) in enumerate(((a, a < b), (b, b > a), (a, a > b),
                                     (b, b < a), (a, a == b))):
        np.add.at(out[:, i], (np.broadcast_to(idx, vals.shape)[sel],
                              vals[sel]), 1.0)
    return out


def _pmf_terms(q: int):
    k = np.arange(q + 2, dtype=np.float64)
    u = np.exp2(-k)
    u[q + 1] = 0.0                      # the top value has no survivor
    d = np.exp2(-k)
    d[q + 1] = 2.0 ** -q                # P(K = q+1) = 1 - exp(-t 2**-q)
    return u, d


def _single_grad(t, c, u, d):
    """Sum over k of c_k d/dt log P(K = k | t), and the log-likelihood."""
    e = -np.expm1(-t[:, None] * d)      # 1 - exp(-t d)
    e[:, 0] = 1.0
    ll = -t[:, None] * u + np.log(np.maximum(e, 1e-300))
    ll[:, 0] = -t
    g = -u + d / np.expm1(t[:, None] * d)
    g[:, 0] = -1.0
    return (c * ll).sum(1), (c * g).sum(1)


def _eq_grad(ta, tb, tx, c, u, d):
    """The A = B term: log-likelihood and gradient in (ta, tb, tx)."""
    tsum = (ta + tb + tx)[:, None]
    ea = np.exp(-(ta + tx)[:, None] * d)
    eb = np.exp(-(tb + tx)[:, None] * d)
    es = np.exp(-tsum * d)
    bracket = (-np.expm1(-(ta + tx)[:, None] * d)) * \
              (-np.expm1(-(tb + tx)[:, None] * d)) + \
        es * (-np.expm1(-tx[:, None] * d))
    bracket = np.maximum(bracket, 1e-300)
    ll = -tsum * u + np.log(bracket)
    ll[:, 0] = -tsum[:, 0]
    ga = -u + d * (ea - es) / bracket
    gb = -u + d * (eb - es) / bracket
    gx = -u + d * (ea + eb - es) / bracket
    for g in (ga, gb, gx):
        g[:, 0] = -1.0
    return ((c * ll).sum(1), (c * ga).sum(1), (c * gb).sum(1),
            (c * gx).sum(1))


def _loglik_grad(theta, stats, q, r):
    """Log-likelihood and its gradient in theta = log(lambda_a, b, x)."""
    u, d = _pmf_terms(q)
    lam = np.exp(theta)
    ta, tb, tx = lam[:, 0] / r, lam[:, 1] / r, lam[:, 2] / r
    l1, g1 = _single_grad(ta + tx, stats[:, 0], u, d)   # A < B: A ~ ta+tx
    l2, g2 = _single_grad(tb, stats[:, 1], u, d)        # A < B: B ~ tb
    l3, g3 = _single_grad(ta, stats[:, 2], u, d)        # A > B: A ~ ta
    l4, g4 = _single_grad(tb + tx, stats[:, 3], u, d)   # A > B: B ~ tb+tx
    l5, ga, gb, gx = _eq_grad(ta, tb, tx, stats[:, 4], u, d)
    d_ta = g1 + g3 + ga
    d_tb = g2 + g4 + gb
    d_tx = g1 + g4 + gx
    grad = np.stack([d_ta * ta, d_tb * tb, d_tx * tx], axis=1)
    return l1 + l2 + l3 + l4 + l5, grad


def _newton(theta, stats, q, r, iters, tol, state_round):
    """Damped Newton ascent of the log-likelihood from ``theta`` [P, 3]."""
    theta = theta.copy()
    lo, hi = np.log(1e-9), np.log(1e15)
    active = np.arange(len(theta))
    h = 1e-6
    for _ in range(iters):
        if not len(active):
            break
        th, st = theta[active], stats[active]
        ll, g = _loglik_grad(th, st, q, r)
        hess = np.empty((len(th), 3, 3))
        for j in range(3):
            step = np.zeros(3)
            step[j] = h
            gp = _loglik_grad(th + step, st, q, r)[1]
            gm = _loglik_grad(th - step, st, q, r)[1]
            hess[:, :, j] = (gp - gm) / (2 * h)
        hess = 0.5 * (hess + hess.transpose(0, 2, 1))
        # mu I - H positive definite, so the step is an ascent direction;
        # it is shortened as a whole (not clipped per rate), keeping that
        mu = np.maximum(0.0, np.linalg.eigvalsh(hess)[:, -1]) + 1e-6 * (
            1.0 + np.abs(np.diagonal(hess, axis1=1, axis2=2)).max(axis=1))
        a = mu[:, None, None] * np.eye(3) - hess
        delta = np.linalg.solve(a, g[:, :, None])[:, :, 0]
        delta *= np.minimum(1.0, 2.0 / np.maximum(
            np.abs(delta).max(axis=1), 1e-300))[:, None]
        delta = np.clip(th + delta, lo, hi) - th
        for _ in range(40):
            worse = _loglik_grad(th + delta, st, q, r)[0] < ll
            if not worse.any():
                break
            delta[worse] *= 0.5
        else:
            delta[_loglik_grad(th + delta, st, q, r)[0] < ll] = 0.0
        theta[active] = (th + delta if state_round is None
                         else state_round(th + delta))
        moved = np.max(np.abs(np.expm1(delta)), axis=1) > tol
        active = active[moved]
    return theta


def intersection_mle(stats: np.ndarray, est_a, est_b, est_u, q: int,
                     r: int, iters: int = 100, tol: float = 1e-10,
                     state_round=None, rates: bool = False) -> np.ndarray:
    """Maximum-likelihood |A ∩ B| per pair, in float64.

    Damped Newton ascent in log space from two starts, keeping per pair
    the one that ends at the higher likelihood: the clipped
    inclusion-exclusion start, and a start with an intersection of 1
    (the likelihood can have a second hill near an empty intersection).
    The Hessian is a central difference of the analytic gradient, and a
    step that lowers the likelihood is halved. Each rate is held in
    ``[1e-9, 1e15]``; a pair stops once its step moves no rate by more
    than ``tol`` of itself. ``state_round``, if given, rounds the
    log-rates after every step (the control holds them in bfloat16).
    ``rates=True`` returns all three rates ``[P, 3]`` (|A \\ B|,
    |B \\ A|, |A ∩ B|).
    """
    x0 = np.maximum(est_a + est_b - est_u, 1.0)
    starts = (np.stack([np.maximum(est_a - x0, 1.0),
                        np.maximum(est_b - x0, 1.0), x0], axis=1),
              np.stack([np.maximum(est_a - 1.0, 1.0),
                        np.maximum(est_b - 1.0, 1.0),
                        np.ones_like(x0)], axis=1))
    best, best_ll = None, None
    for start in starts:
        theta = _newton(np.log(start), stats, q, r, iters, tol, state_round)
        ll = _loglik_grad(theta, stats, q, r)[0]
        if best is None:
            best, best_ll = theta, ll
        else:
            better = ll > best_ll
            best[better], best_ll = theta[better], np.maximum(ll, best_ll)
    return np.exp(best) if rates else np.exp(best[:, 2])
