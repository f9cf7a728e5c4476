"""Pallas TPU kernel: fused hash + HLL scatter-max accumulation.

Semantics = Algorithm 1 INSERT: for each edge e with mask[e],
``regs[rows[e], bucket(keys[e])] max= rho(keys[e])`` — with the
``core.hashing.bucket_rho`` split computed *inside* the kernel body.
The old pipeline hashed every key in one XLA program, wrote the
(bucket, rho) streams to HBM, and re-read them in the scatter kernel;
fusing the hash keeps the edge stream's derived values in registers and
halves the per-edge HBM traffic to just (row, key).

TPU design (DESIGN.md §9/§11): the register panel (V, w) lives in VMEM
for the whole grid (a pinned single-buffered block; ``registry.resolve``
bounds its bytes), copied in from its aliased input at grid step 0. Edge
rows, raw uint32 keys and the padding mask are scalars in SMEM. Each edge
is one aligned-tile read-modify-write (``kernels.tiles``): a one-hot
(row, bucket) * rho update built from 2-D iotas, merged in int32 and
narrowed back on store. Masked/padding edges zero the rho and park on
row 0: max with 0 is a no-op, so the kernel needs no branch.

Packed layout (DESIGN.md §11): the tile holds half-width packed bytes;
the update places rho (saturated at 15) in the low or high nibble of its
byte, and the merge is nibble-wise — the full-width row never exists.

The sequential fori_loop over the edge block is the TPU-idiomatic
scatter: TPU has no atomic scatter; grid steps run sequentially per
core, and the register panel is input_output_aliased so updates
accumulate in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import bucket_rho32
from repro.kernels import packing, tiles

__all__ = ["hll_accumulate"]

#: edges per grid step: a multiple of 1024, the tile of a 1-D int32 SMEM
#: block in XLA's layout.
DEFAULT_EDGE_BLOCK = 1024


def _make_kernel(p: int, seed: int, layout: str):
    sat = packing.SATURATION if layout == "packed" else None

    def _kernel(regs_ref, rows_ref, keys_ref, mask_ref, out_ref):
        # A TPU pipeline copies output blocks out, never in: fill the
        # VMEM-resident panel from its (aliased) input once.
        @pl.when(pl.program_id(0) == 0)
        def _():
            pltpu.sync_copy(regs_ref, out_ref)

        shape = (tiles.tile_rows(out_ref.dtype), out_ref.shape[1])
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        w = shape[1]

        def body(e, _):
            # Fused hash: bucket/rho from the raw key, in-register.
            bucket, rho = bucket_rho32(keys_ref[e], p, seed)
            keep = mask_ref[e] != 0
            rho = jnp.where(keep, rho, 0)
            row = jnp.where(keep, rows_ref[e], 0)
            if sat is not None:
                # split-half nibbles: bucket b < w is the low lane of byte
                # b, bucket b >= w the high lane of byte b - w.
                rho = jnp.minimum(rho, sat)
                update = (jnp.where(lane == bucket, rho, 0)
                          | (jnp.where(lane == bucket - w, rho, 0)
                             << packing.LANE_BITS))
            else:
                update = jnp.where(lane == bucket, rho, 0)
            start = tiles.tile_start(row, shape[0])
            cur = tiles.load_tile(out_ref, start)
            update = jnp.where(sub == row - start, update, 0)
            tiles.store_tile(out_ref, start, tiles.merge(cur, update, layout))
            return 0

        jax.lax.fori_loop(0, rows_ref.shape[0], body, 0)
    return _kernel


@functools.partial(jax.jit, static_argnames=("p", "seed", "layout",
                                             "edge_block", "interpret"))
def hll_accumulate(regs: jax.Array, rows: jax.Array, keys: jax.Array,
                   mask: jax.Array, *, p: int, seed: int = 0,
                   layout: str = "byte",
                   edge_block: int = DEFAULT_EDGE_BLOCK,
                   interpret: bool) -> jax.Array:
    """regs: uint8[V, w]; rows: int32[E]; keys: uint32[E]; mask: bool[E].

    V must be a multiple of ``tiles.tile_rows(uint8)`` and E of
    edge_block (ops.py pads; padding edges carry mask=False). Returns the
    updated panel in the same layout.
    """
    v, w = regs.shape
    e = rows.shape[0]
    assert e % edge_block == 0, (e, edge_block)
    assert v % tiles.tile_rows(regs.dtype) == 0, v
    grid = (e // edge_block,)
    edges = pl.BlockSpec((edge_block,), lambda i: (i,),
                         memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _make_kernel(p, seed, layout),
        grid=grid,
        in_specs=[tiles.pinned((v, w)), edges, edges, edges],
        out_specs=tiles.pinned((v, w)),
        out_shape=jax.ShapeDtypeStruct((v, w), jnp.uint8),
        input_output_aliases={0: 0},
        compiler_params=tiles.COMPILER_PARAMS,
        interpret=interpret,
        name="hll_accumulate",
    )(regs, rows.astype(jnp.int32), keys.astype(jnp.uint32),
      mask.astype(jnp.int32))
