"""Pallas TPU kernel: fused batch-HIP increments between hop panels.

Semantics = ref.hip_delta_ref: per register row, sum the inverse change
probabilities ``2**prev_j`` over every register the hop grew
(``cur_j > prev_j``) — the ADS family's per-hop HIP delta
(``core.ads``, DESIGN.md §13). One pass over both panels, fused compare
+ exp2 + lane reduction, so the D^{t-1}/D^t panels are read once and no
intermediate mask/weight panel hits HBM.

TPU design: grid over row blocks; each block holds two (BN, r) uint8
panels in VMEM reduced lane-wise by the VPU (exp2 of a uint8 upcast is
a cheap transcendental, like the estimate kernel). Output is a (BN, 1)
f32 panel to keep the store 2-D and lane-aligned. Byte layout only —
ADS registers are never packed (4-bit saturation corrupts the ``2**x``
weights), so there is no unpack path in this body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["hip_delta_rows"]

DEFAULT_ROW_BLOCK = 256


def _kernel(prev_ref, cur_ref, out_ref):
    # uint8 lanes have no vector compare or reduction: widen first
    prev = prev_ref[...].astype(jnp.int32)
    cur = cur_ref[...].astype(jnp.int32)
    inv_p = jnp.exp2(prev.astype(jnp.float32))
    grew = (cur > prev).astype(jnp.float32)
    out_ref[...] = jnp.sum(inv_p * grew, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def hip_delta_rows(prev: jax.Array, cur: jax.Array, *,
                   row_block: int = DEFAULT_ROW_BLOCK,
                   interpret: bool) -> jax.Array:
    """prev/cur: uint8[N, r] (N multiple of row_block) -> float32[N]."""
    n, r = prev.shape
    assert prev.shape == cur.shape, (prev.shape, cur.shape)
    assert n % row_block == 0, (n, row_block)
    grid = (n // row_block,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((row_block, r), lambda i: (i, 0)),
                  pl.BlockSpec((row_block, r), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
        name="hip_delta_rows",
    )(prev, cur)
    return out[:, 0]
