"""In-kernel row access and register math in the forms Mosaic compiles.

Every kernel that walks register rows at data-dependent indices shares
three constraints of the TPU compiler:

* A dynamic slice of a VMEM ref must start on a sublane-tile boundary
  the compiler can prove (8 rows of 32-bit data, 32 rows of uint8). A
  row is therefore read or written by loading the aligned tile that
  holds it (:func:`tile_start`) and selecting the row with a sublane
  iota.
* Vector max and reductions are not lowered for unsigned 8-bit lanes, so
  register tiles are widened to int32 on load and narrowed on store.
* Packed panels (``kernels.packing``: two 4-bit lanes per byte) merge
  nibble-wise; :func:`merge` and :func:`unpack` do that in int32.

These helpers are traced inside kernel bodies; in interpret mode they run
the same jnp code, so the ref oracles stay the contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import packing

__all__ = ["VMEM_LIMIT_BYTES", "PANEL_VMEM_BYTES", "COMPILER_PARAMS",
           "pinned", "tile_rows", "tile_start", "load_tile", "store_tile",
           "read_row", "put_row", "merge", "unpack", "harmonic", "columns"]

_NIB = packing.LANE_BITS
_LO = (1 << _NIB) - 1


#: scoped VMEM a kernel may claim (v5e has 128 MiB per core; the default
#: scope is 16 MiB). Checked by compiling for v5e in tests/test_tpu_compile.
VMEM_LIMIT_BYTES = 100 * 2**20

#: largest register panel (bytes) a pallas engine may hold. Accumulate and
#: propagate pin two panels (input and output) in VMEM, the query kernels
#: one. Compiled for v5e: a 52 MiB panel overflows the scope in
#: accumulate/propagate, and at p=10 packed so does a 50 MiB one (by the
#: row tiles' working set), so the bound keeps 2 MiB of headroom.
PANEL_VMEM_BYTES = 48 * 2**20

#: grid steps run in order: the pinned panels accumulate across them.
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def pinned(shape: tuple[int, ...]) -> pl.BlockSpec:
    """A whole-array block held in VMEM for the entire grid, single-buffered
    (its block index never changes, so a second buffer would be waste)."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape),
                        pipeline_mode=pl.Buffered(1))


def tile_rows(dtype) -> int:
    """Sublane rows of one native TPU tile for ``dtype`` (8 for 32-bit)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def tile_start(row: jax.Array, rows: int) -> jax.Array:
    """First row of the ``rows``-aligned tile holding ``row``."""
    return pl.multiple_of((row // rows) * rows, rows)


def load_tile(ref, start: jax.Array) -> jax.Array:
    """The aligned tile at ``start`` of a 2-D ref, widened to int32."""
    rows = tile_rows(ref.dtype)
    return ref[pl.ds(start, rows), :].astype(jnp.int32)


def store_tile(ref, start: jax.Array, tile: jax.Array) -> None:
    """Narrow an int32 tile back to the ref's dtype and store it."""
    rows = tile_rows(ref.dtype)
    ref[pl.ds(start, rows), :] = tile.astype(ref.dtype)


def _hit(start, row, shape) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) == row - start


def read_row(ref, row: jax.Array) -> jax.Array:
    """Row ``row`` of a 2-D ref as an int32 ``(1, w)`` vector."""
    start = tile_start(row, tile_rows(ref.dtype))
    tile = load_tile(ref, start)
    picked = jnp.where(_hit(start, row, tile.shape), tile, 0)
    return jnp.max(picked, axis=0, keepdims=True)  # registers are >= 0


def put_row(ref, row: jax.Array, value: jax.Array) -> None:
    """Write the int32 ``(1, w)`` ``value`` into row ``row`` of ``ref``."""
    start = tile_start(row, tile_rows(ref.dtype))
    tile = load_tile(ref, start)
    store_tile(ref, start, jnp.where(_hit(start, row, tile.shape),
                                     value, tile))


def merge(a: jax.Array, b: jax.Array, layout: str) -> jax.Array:
    """HLL register max of two int32 panels, byte-wise or nibble-wise."""
    if layout != "packed":
        return jnp.maximum(a, b)
    lo = jnp.maximum(a & _LO, b & _LO)
    hi = jnp.maximum(a >> _NIB, b >> _NIB)
    return lo | (hi << _NIB)


def unpack(x: jax.Array, layout: str) -> jax.Array:
    """Register values of an int32 panel: split-half nibbles when packed."""
    if layout != "packed":
        return x
    return jnp.concatenate([x & _LO, x >> _NIB], axis=-1)


def harmonic(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row ``(sum 2^-reg, #zero registers)`` of int32 registers."""
    f = x.astype(jnp.float32)
    s = jnp.sum(jnp.exp2(-f), axis=1, keepdims=True)
    z = jnp.sum((x == 0).astype(jnp.float32), axis=1, keepdims=True)
    return s, z


def columns(cols: list[jax.Array]) -> jax.Array:
    """Assemble ``(n, 1)`` columns into one ``(n, len(cols))`` block.

    Kernels write their statistics with one whole-block store instead of
    a store per column.
    """
    n = cols[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, len(cols)), 1)
    out = jnp.zeros((n, len(cols)), jnp.float32)
    for j, c in enumerate(cols):
        out = jnp.where(lane == j, c, out)
    return out
