"""Pallas TPU kernel: HLL row gather-max propagation (Algorithm 2 hot loop).

Semantics = ref.hll_propagate_ref: out[dst[e]] max= regs_src[src[e]], with
reads frozen at D^{t-1} (regs_src is never written; the output starts as
its copy — Algorithm 2 line 23's ``D^t <- D^{t-1}``).

TPU design: both the frozen source panel and the accumulating output panel
are pinned in VMEM (``registry.resolve`` bounds the panel bytes). Each
edge reads its source row out of the aligned tile that holds it and
max-merges it into the destination's tile (``kernels.tiles``) — all
VPU work in int32; no gather/scatter HLO. Padding edges use
src = dst = 0: since out[0] only ever grows above its initial copy of
regs_src[0], max(out[0], regs_src[0]) is a provable no-op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles

__all__ = ["hll_propagate"]

#: edges per grid step: a multiple of 1024, the tile of a 1-D int32 SMEM
#: block in XLA's layout.
DEFAULT_EDGE_BLOCK = 1024


def _make_kernel(layout: str):
    def _kernel(src_regs_ref, src_ref, dst_ref, out_ref):
        # D^t starts as a copy of D^{t-1} (Algorithm 2 line 23), made in
        # VMEM once: a TPU pipeline copies output blocks out, never in.
        @pl.when(pl.program_id(0) == 0)
        def _():
            pltpu.sync_copy(src_regs_ref, out_ref)

        rows = tiles.tile_rows(out_ref.dtype)
        sub = jax.lax.broadcasted_iota(jnp.int32, (rows, out_ref.shape[1]), 0)

        def body(e, _):
            # Packed panels merge nibble-wise (tiles.merge): a byte-wise
            # max would pick one whole byte and lose the larger of the two
            # 4-bit lanes held by the other operand.
            v_src = tiles.read_row(src_regs_ref, src_ref[e])
            d = dst_ref[e]
            start = tiles.tile_start(d, rows)
            cur = tiles.load_tile(out_ref, start)
            v_src = jnp.where(sub == d - start, v_src, 0)
            tiles.store_tile(out_ref, start, tiles.merge(cur, v_src, layout))
            return 0

        jax.lax.fori_loop(0, src_ref.shape[0], body, 0)
    return _kernel


@functools.partial(jax.jit, static_argnames=("layout", "edge_block",
                                             "interpret"))
def hll_propagate(regs: jax.Array, src: jax.Array, dst: jax.Array,
                  *, layout: str = "byte",
                  edge_block: int = DEFAULT_EDGE_BLOCK,
                  interpret: bool) -> jax.Array:
    """regs: uint8[V, w]; src/dst: int32[E] (E multiple of edge_block, V of
    ``tiles.tile_rows(uint8)``).

    Returns D^t = D^{t-1} merged with gathered neighbor rows (same
    layout as the input panel).
    """
    v, r = regs.shape
    e = src.shape[0]
    assert e % edge_block == 0, (e, edge_block)
    assert v % tiles.tile_rows(regs.dtype) == 0, v
    grid = (e // edge_block,)
    edges = pl.BlockSpec((edge_block,), lambda i: (i,),
                         memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _make_kernel(layout),
        grid=grid,
        in_specs=[tiles.pinned((v, r)), edges, edges],   # frozen D^{t-1}
        out_specs=tiles.pinned((v, r)),                  # D^t accumulator
        out_shape=jax.ShapeDtypeStruct((v, r), jnp.uint8),
        compiler_params=tiles.COMPILER_PARAMS,
        interpret=interpret,
        name="hll_propagate",
    )(regs, src.astype(jnp.int32), dst.astype(jnp.int32))
