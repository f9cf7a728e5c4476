"""Pallas TPU kernel: fused union cardinality statistics (DESIGN.md §10).

Semantics = ref.union_estimate_ref: for each padded id set (one row of a
bucketed (B, L) id panel), gather the member sketches, lane-wise max-merge
them, and reduce the merged row to the harmonic statistics (s, z) — in one
pass, without the merged register panel ever leaving the chip. The O(B)
estimator combination (Flajolet / linear counting / beta) stays outside
the kernel behind the ``hll.estimate_from_stats`` seam.

TPU design: the register panel (V, w) is pinned in VMEM for the whole grid
(same contract as accumulate/propagate: ``registry.resolve`` bounds its
bytes); the ids are scalars in SMEM, a masked lane carrying -1. Each grid
step owns a block of set rows: a fori_loop walks the block's lanes,
reading each member row out of its aligned tile (``kernels.tiles``) and
max-merging it into an int32 (set_block, w) carry — a masked lane merges
the empty row (never vertex 0's sketch) — then one vectorized VPU
reduction turns the merged panel into the (s, z) output columns. HBM
traffic is w bytes per *member*, in and nothing out but 8 bytes per set;
the old two-pass path wrote and re-read the whole merged (B, r) panel
between its gather and estimate programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles

__all__ = ["union_estimate_stats"]

DEFAULT_SET_BLOCK = 8


def _make_kernel(layout: str):
    def _kernel(regs_ref, ids_ref, out_ref):
        bb, lanes = ids_ref.shape
        sub = jax.lax.broadcasted_iota(jnp.int32, (bb, regs_ref.shape[1]), 0)

        def member(e, acc):
            b = e // lanes
            gid = ids_ref[b, e % lanes]  # < 0: masked lane
            row = tiles.read_row(regs_ref, jnp.maximum(gid, 0))
            row = jnp.where((sub == b) & (gid >= 0), row, 0)
            return tiles.merge(acc, row, layout)

        acc = jax.lax.fori_loop(0, bb * lanes, member,
                                jnp.zeros(sub.shape, jnp.int32))
        s, z = tiles.harmonic(tiles.unpack(acc, layout))
        out_ref[...] = tiles.columns([s, z])
    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("layout", "set_block", "interpret"))
def union_estimate_stats(regs: jax.Array, ids: jax.Array, mask: jax.Array,
                         *, layout: str = "byte",
                         set_block: int = DEFAULT_SET_BLOCK,
                         interpret: bool) -> jax.Array:
    """regs: uint8[V, w]; ids: int32[B, L]; mask: bool[B, L] (B a multiple
    of set_block, V of ``tiles.tile_rows(uint8)``) -> float32[B, 2] =
    (s, z) of each masked union row."""
    v, r = regs.shape
    b, lanes = ids.shape
    assert mask.shape == (b, lanes), (mask.shape, ids.shape)
    assert b % set_block == 0, (b, set_block)
    assert v % tiles.tile_rows(regs.dtype) == 0, v
    grid = (b // set_block,)
    # the mask rides in the ids: a masked lane carries -1
    ids_m = jnp.where(mask, ids.astype(jnp.int32), -1)
    return pl.pallas_call(
        _make_kernel(layout),
        grid=grid,
        in_specs=[
            tiles.pinned((v, r)),  # panel pinned in VMEM
            pl.BlockSpec((set_block, lanes), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((set_block, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 2), jnp.float32),
        compiler_params=tiles.COMPILER_PARAMS,
        interpret=interpret,
        name="union_estimate_stats",
    )(regs, ids_m)
