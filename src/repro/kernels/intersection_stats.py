"""Pallas TPU kernel: fused intersection pair statistics (DESIGN.md §10).

Semantics = ref.intersection_stats_ref: for each pair (x, y) of a padded
pair lane, gather the two sketches and emit everything the T̃(xy)
estimator tail consumes — the Eq. 19 count histograms float32[B, 5, q+2]
*and* the harmonic (s, z) statistics of A, B and A ∪ B (the Newton
initializer / inclusion-exclusion inputs) — in one pass. The gathered and
merged register panels live only in VMEM scratch; the old path
materialized both (B, r) gather panels in HBM before the separate
``ertl_stats`` and estimate programs re-read them.

TPU design: register panel (V, w) pinned in VMEM; pair endpoints as SMEM
scalars. Each grid step gathers its pair block into two int32
(pair_block, w) VMEM scratch panels with a fori_loop of aligned-tile row copies
(``kernels.tiles``), then runs the vectorized panel math of the
``ertl_stats`` kernel (``pair_stats``) plus the three (s, z) reductions —
all VPU work on VMEM-resident panels, no gather HLO.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles
from repro.kernels.ertl_stats import pair_stats

__all__ = ["intersection_stats"]

DEFAULT_PAIR_BLOCK = 64


def _make_kernel(q: int, layout: str):
    def _kernel(regs_ref, pairs_ref, stats_ref, sz_ref, a_ref, b_ref):
        def gather(e, _):
            tiles.put_row(a_ref, e, tiles.read_row(regs_ref, pairs_ref[e, 0]))
            tiles.put_row(b_ref, e, tiles.read_row(regs_ref, pairs_ref[e, 1]))
            return 0

        jax.lax.fori_loop(0, pairs_ref.shape[0], gather, 0)
        # The gather moved w-byte rows; the histogram and (s, z) math needs
        # register values, so unpack in VMEM (§11).
        ai = tiles.unpack(a_ref[...], layout)
        bi = tiles.unpack(b_ref[...], layout)
        stats_ref[...] = pair_stats(ai, bi, q)
        sz = [c for panel in (ai, bi, jnp.maximum(ai, bi))
              for c in tiles.harmonic(panel)]
        sz_ref[...] = tiles.columns(sz)
    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("q", "layout", "pair_block", "interpret"))
def intersection_stats(regs: jax.Array, pairs: jax.Array, q: int,
                       *, layout: str = "byte",
                       pair_block: int = DEFAULT_PAIR_BLOCK,
                       interpret: bool) -> tuple[jax.Array, jax.Array]:
    """regs: uint8[V, w]; pairs: int32[B, 2] endpoints (B a multiple of
    pair_block, V of ``tiles.tile_rows(uint8)``) -> (float32[B, 5, q+2]
    Eq. 19 stats, float32[B, 3, 2] (s, z) panels)."""
    v, r = regs.shape
    b = pairs.shape[0]
    assert b % pair_block == 0, (b, pair_block)
    assert v % tiles.tile_rows(regs.dtype) == 0, v
    grid = (b // pair_block,)
    k = 5 * (q + 2)
    stats, sz = pl.pallas_call(
        _make_kernel(q, layout),
        grid=grid,
        in_specs=[
            tiles.pinned((v, r)),  # panel pinned in VMEM
            pl.BlockSpec((pair_block, 2), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((pair_block, k), lambda i: (i, 0)),
            pl.BlockSpec((pair_block, 6), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 6), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((pair_block, r), jnp.int32),
                        pltpu.VMEM((pair_block, r), jnp.int32)],
        compiler_params=tiles.COMPILER_PARAMS,
        interpret=interpret,
        name="intersection_stats",
    )(regs, pairs.astype(jnp.int32))
    return stats.reshape(b, 5, q + 2), sz.reshape(b, 3, 2)
