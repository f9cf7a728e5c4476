"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function here defines the exact semantics its kernel must reproduce;
tests sweep shapes/dtypes and assert_allclose (exact equality for the
integer register kernels) between kernel and oracle.

Each oracle runs under a ``jax.named_scope`` named after its kernel op
(``accumulate``, ``propagate``, ``estimate_rows``, ``union_estimate``,
``intersection_stats``, ``hip_delta``), so a profile's op names say which
op an XLA fusion belongs to (``jit_plan_union/union_estimate/...``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "hll_accumulate_ref", "hll_propagate_ref", "hll_estimate_ref",
    "ertl_stats_ref", "union_estimate_ref", "intersection_stats_ref",
    "hip_delta_ref",
]


def hip_delta_ref(prev: jax.Array, cur: jax.Array) -> jax.Array:
    """Batch-HIP increments: sum_j [cur_j > prev_j] * 2^prev_j per row.

    ADS-family oracle (repro.core.ads.hip_delta semantics): the summed
    inverse change probabilities of every register a hop grew, evaluated
    against the pre-hop value. prev/cur: uint8[N, r] byte-layout panels
    with cur >= prev element-wise -> float32[N].
    """
    with jax.named_scope("hip_delta"):
        grew = cur > prev
        inv_p = jnp.exp2(prev.astype(jnp.float32))
        return jnp.sum(jnp.where(grew, inv_p, 0.0), axis=-1)


def hll_accumulate_ref(regs: jax.Array, rows: jax.Array, buckets: jax.Array,
                       rhos: jax.Array) -> jax.Array:
    """Scatter-max: regs[rows[e], buckets[e]] <- max(., rhos[e]).

    Padding convention: rho == 0 entries are no-ops (empty register value).
    regs: uint8[V, r]; rows/buckets: int32[E]; rhos: uint8[E].
    """
    with jax.named_scope("accumulate"):
        return regs.at[rows, buckets].max(rhos)


def hll_propagate_ref(regs: jax.Array, src: jax.Array, dst: jax.Array,
                      mask: jax.Array) -> jax.Array:
    """Row gather-max: out[dst[e]] <- max(out[dst[e]], regs[src[e]]).

    Reads always come from the *input* regs (the frozen D^{t-1}); the output
    starts as a copy of regs (Algorithm 2 line 23). mask=False rows no-op.
    """
    with jax.named_scope("propagate"):
        gathered = jnp.where(mask[:, None], regs[src], jnp.uint8(0))
        return regs.at[dst].max(gathered)


def hll_estimate_ref(regs: jax.Array, alpha: float) -> tuple[jax.Array, jax.Array]:
    """Fused harmonic statistics: (sum 2^-reg, zero count) per sketch row.

    regs: uint8[N, r] -> (float32[N], float32[N]). The final estimator
    combination (raw vs linear counting vs beta) happens outside the kernel
    — it is O(N) scalar work; the O(N*r) register reduction is the hot part.
    ``alpha`` is threaded for the fused raw estimate output convenience.
    """
    with jax.named_scope("estimate_rows"):
        x = regs.astype(jnp.float32)
        s = jnp.sum(jnp.exp2(-x), axis=-1)
        z = jnp.sum(regs == 0, axis=-1).astype(jnp.float32)
        return s, z


def union_estimate_ref(regs: jax.Array, ids: jax.Array, mask: jax.Array,
                       ) -> tuple[jax.Array, jax.Array]:
    """Fused union statistics: (s, z) of the masked lane-wise row max.

    regs: uint8[V, r]; ids: int32[B, L]; mask: bool[B, L] ->
    (float32[B], float32[B]). Masked-out lanes contribute the empty row
    (never vertex 0's registers); a fully masked set row reduces to the
    empty sketch. This is the exact computation of the old two-pass union
    plan (gather -> where(mask) -> max -> harmonic stats), restructured so
    a kernel can keep the merged rows on-chip.
    """
    with jax.named_scope("union_estimate"):
        rows = jnp.where(mask[:, :, None], regs[ids], jnp.uint8(0))
        return hll_estimate_ref(jnp.max(rows, axis=1), 0.0)


def intersection_stats_ref(regs: jax.Array, pa: jax.Array, pb: jax.Array,
                           q: int) -> tuple[jax.Array, jax.Array]:
    """Fused pair statistics: Eq. 19 histograms + (s, z) for A, B, A ∪ B.

    regs: uint8[V, r]; pa/pb: int32[B] (pair endpoints) ->
    (float32[B, 5, q+2], float32[B, 3, 2]). The sz panel is stacked
    [(s_a, z_a), (s_b, z_b), (s_union, z_union)] — everything the MLE /
    inclusion-exclusion tail (``intersection.estimate_from_pair_stats``)
    needs, so the gathered register panels never leave the kernel.
    Padding pairs gather row 0 like the old two-pass plan did; the caller
    masks the final estimates.
    """
    with jax.named_scope("intersection_stats"):
        a, b = regs[pa], regs[pb]
        stats = ertl_stats_ref(a, b, q)
        s_a, z_a = hll_estimate_ref(a, 0.0)
        s_b, z_b = hll_estimate_ref(b, 0.0)
        s_u, z_u = hll_estimate_ref(jnp.maximum(a, b), 0.0)
        sz = jnp.stack([jnp.stack([s_a, z_a], axis=-1),
                        jnp.stack([s_b, z_b], axis=-1),
                        jnp.stack([s_u, z_u], axis=-1)], axis=-2)
        return stats, sz


def ertl_stats_ref(a: jax.Array, b: jax.Array, q: int) -> jax.Array:
    """Eq. (19) count statistics. a, b: uint8[E, r] -> float32[E, 5, q+2].

    Order: [c_a_lt, c_a_gt, c_b_lt, c_b_gt, c_eq] — see
    repro.core.intersection.ertl_stats (this is its per-pair kernel form).
    """
    ks = jnp.arange(q + 2, dtype=jnp.int32)
    ai = a.astype(jnp.int32)[..., None]
    bi = b.astype(jnp.int32)[..., None]
    oh_a = (ai == ks).astype(jnp.float32)
    oh_b = (bi == ks).astype(jnp.float32)
    lt = (ai < bi).astype(jnp.float32)
    gt = (ai > bi).astype(jnp.float32)
    eq = (ai == bi).astype(jnp.float32)
    return jnp.stack([
        jnp.sum(oh_a * lt, axis=-2),
        jnp.sum(oh_a * gt, axis=-2),
        jnp.sum(oh_b * gt, axis=-2),
        jnp.sum(oh_b * lt, axis=-2),
        jnp.sum(oh_a * eq, axis=-2),
    ], axis=-2)
