"""Pallas TPU kernel: Ertl register-pair count statistics (Eq. 19).

Semantics = ref.ertl_stats_ref: for each sketch pair (a_i, b_i), histogram
the register values into [c_a_lt, c_a_gt, c_b_lt, c_b_gt, c_eq] over
k in [0, q+2). This is the O(E*r) front of every T̃(xy) intersection
estimate (Algorithms 4/5); the 3-parameter MLE that follows is O(E*q).

TPU design: grid over edge-pair blocks; panels (BE, w) uint8 for a and b in
VMEM, widened to int32 (and unpacked) in the body. The comparison masks
lt/gt/eq are computed once per panel; the k-loop is a static unroll (q+2
iterations) of lane-wise masked reductions — each iteration is (BE, r)
compares + adds on the VPU, giving five columns of one lane-dense
(BE, 5*(q+2)) output block. No gather, no scatter, no MXU needed; arithmetic
intensity ~ (q+2) ops/byte keeps it compute-dense for VMEM-resident panels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiles

__all__ = ["ertl_stats", "pair_stats"]

DEFAULT_PAIR_BLOCK = 128


def pair_stats(ai: jax.Array, bi: jax.Array, q: int) -> jax.Array:
    """Eq. 19 histograms of int32 register panels (BE, r) -> (BE, 5*(q+2)).

    Column ``j * (q + 2) + k`` holds statistic j at register value k; the
    caller reshapes to (BE, 5, q+2). Comparison masks are computed once;
    the k-loop is a static unroll of lane-wise masked reductions.
    """
    # Compare through the difference: Mosaic narrows a compare of two
    # widened uint8 panels back to uint8, which it cannot lower.
    d = ai - bi
    lt = (d < 0).astype(jnp.float32)
    gt = (d > 0).astype(jnp.float32)
    eq = (d == 0).astype(jnp.float32)
    cols = [[] for _ in range(5)]
    for k in range(q + 2):  # static unroll: k is a compile-time constant
        a_is_k = (ai == k).astype(jnp.float32)
        b_is_k = (bi == k).astype(jnp.float32)
        for j, x in enumerate((a_is_k * lt, a_is_k * gt, b_is_k * gt,
                               b_is_k * lt, a_is_k * eq)):
            cols[j].append(jnp.sum(x, axis=1, keepdims=True))
    return tiles.columns([c for stat in cols for c in stat])


def _make_kernel(q: int, layout: str):
    def _kernel(a_ref, b_ref, out_ref):
        ai = tiles.unpack(a_ref[...].astype(jnp.int32), layout)
        bi = tiles.unpack(b_ref[...].astype(jnp.int32), layout)
        out_ref[...] = pair_stats(ai, bi, q)
    return _kernel


@functools.partial(jax.jit, static_argnames=("q", "layout", "pair_block",
                                             "interpret"))
def ertl_stats(a: jax.Array, b: jax.Array, q: int,
               *, layout: str = "byte",
               pair_block: int = DEFAULT_PAIR_BLOCK,
               interpret: bool) -> jax.Array:
    """a, b: uint8[E, w] (E multiple of pair_block) -> float32[E, 5, q+2]."""
    e, r = a.shape
    assert a.shape == b.shape
    assert e % pair_block == 0, (e, pair_block)
    grid = (e // pair_block,)
    k = 5 * (q + 2)
    out = pl.pallas_call(
        _make_kernel(q, layout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((pair_block, r), lambda i: (i, 0)),
            pl.BlockSpec((pair_block, r), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((pair_block, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e, k), jnp.float32),
        interpret=interpret,
        name="ertl_stats",
    )(a, b)
    return out.reshape(e, 5, q + 2)
