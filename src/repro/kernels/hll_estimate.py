"""Pallas TPU kernel: fused HLL estimate statistics (harmonic sum + zeros).

Semantics = ref.hll_estimate_ref: per sketch row, s = sum_i 2^{-reg_i} and
z = #zero registers, fused in one pass over the register panel. The O(N)
estimator tail (alpha*r^2/s vs linear counting vs beta) stays outside — it
is negligible and branchy.

TPU design: grid over row blocks; each block is a (BN, w) uint8 panel in
VMEM, widened to int32 and reduced lane-wise by the VPU (exp2 of the
upcast is a cheap transcendental; reductions along lanes). Output is a
(BN, 2) f32 panel (s in column 0, z in column 1), written as one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiles

__all__ = ["hll_estimate_stats"]

DEFAULT_ROW_BLOCK = 256


def _make_kernel(layout: str):
    def _kernel(regs_ref, out_ref):
        # unpack-in-VMEM (DESIGN.md §11): HBM moved the half-width panel;
        # the full-width lanes exist only inside this block.
        regs = tiles.unpack(regs_ref[...].astype(jnp.int32), layout)
        out_ref[...] = tiles.columns(list(tiles.harmonic(regs)))
    return _kernel


@functools.partial(jax.jit, static_argnames=("layout", "row_block",
                                             "interpret"))
def hll_estimate_stats(regs: jax.Array, *, layout: str = "byte",
                       row_block: int = DEFAULT_ROW_BLOCK,
                       interpret: bool) -> jax.Array:
    """regs: uint8[N, w] (N multiple of row_block) -> float32[N, 2] = (s, z)."""
    n, r = regs.shape
    assert n % row_block == 0, (n, row_block)
    grid = (n // row_block,)
    return pl.pallas_call(
        _make_kernel(layout),
        grid=grid,
        in_specs=[pl.BlockSpec((row_block, r), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((row_block, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 2), jnp.float32),
        interpret=interpret,
        name="hll_estimate_stats",
    )(regs)
