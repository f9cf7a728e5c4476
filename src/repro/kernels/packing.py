"""Sub-byte register packing: 4-bit HLL lanes, two registers per byte.

HLL registers need at most 6 bits (rho <= q + 1 = 65 - p), but every
kernel historically moved a full byte per register. The ``packed`` layout
stores two registers per byte in 4-bit lanes, halving the HBM bytes each
register panel costs (DESIGN.md §11); the ``byte`` layout remains the
exact-width escape hatch (``REPRO_LAYOUT=byte``, or ``layout="byte"`` at
``engine.open``).

Lane layout is **split-half**: for a row of ``r`` registers, byte ``j``
holds register ``j`` in its low nibble and register ``j + r/2`` in its
high nibble.  Pack/unpack are then two vectorized shifts and a
concatenation — no interleaving gathers — and any fixed permutation of
registers is invariant for every estimator in the repo (harmonic sums,
zero counts and the Eq. 19 histograms are all permutation-symmetric).

Saturation semantics: a 4-bit lane holds values 0..15, so packing clamps
``reg -> min(reg, 15)``.  Clamping commutes *exactly* with the HLL merge
operator — ``min(max(a, b), 15) == max(min(a, 15), min(b, 15))`` — so
pack-then-max equals max-then-pack for **all** register values (the
property suite asserts this), and any sequence of packed merges equals
the packed image of the byte-layout result. Estimates are bit-identical
to the byte layout whenever no register exceeds 15, i.e. until some key
hashes 15 leading zero bits into one bucket (probability ``2^-15`` per
insert); past that point the packed estimate is biased low by at most
``2^-15`` per saturated register in the harmonic sum. Workloads that
need exactness at extreme cardinalities use ``layout="byte"``.

Every function here is pure jnp on arrays, so the same helpers run on
host panels and inside jitted plans. Pallas kernel bodies unpack and
merge in VMEM with the int32 forms of ``kernels.tiles`` (the in-kernel
unpack of DESIGN.md §11): Mosaic has no vector shifts or max on uint8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LAYOUTS", "LANE_BITS", "LANES_PER_BYTE", "SATURATION",
    "validate_layout", "row_width", "pack_rows", "unpack_rows",
    "max_rows", "merge_rows", "scatter_max_rows", "to_layout",
]

#: supported register-panel layouts: one byte per register ("byte") or
#: two 4-bit lanes per byte ("packed").
LAYOUTS = ("byte", "packed")

#: bits per packed register lane.
LANE_BITS = 4

#: registers stored per byte in the packed layout.
LANES_PER_BYTE = 2

#: largest register value a packed lane can hold; packing clamps to it.
SATURATION = (1 << LANE_BITS) - 1

_LO = np.uint8(0x0F)


def validate_layout(layout: str) -> str:
    """Return ``layout`` if supported, else raise ``ValueError``."""
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
    return layout


def row_width(r: int, layout: str) -> int:
    """Bytes per register row of ``r`` registers under ``layout``."""
    validate_layout(layout)
    if layout == "byte":
        return r
    if r % LANES_PER_BYTE:
        raise ValueError(f"packed layout needs an even register count, "
                         f"got r={r}")
    return r // LANES_PER_BYTE


def pack_rows(regs: jax.Array) -> jax.Array:
    """Pack byte-layout rows ``uint8[..., r]`` to ``uint8[..., r/2]``.

    Split-half lanes: ``out[..., j] = min(regs[..., j], 15) |
    (min(regs[..., j + r/2], 15) << 4)``. Values above :data:`SATURATION`
    clamp (see the module docstring for why that is merge-exact).
    """
    r = regs.shape[-1]
    if r % LANES_PER_BYTE:
        raise ValueError(f"cannot pack an odd register count, got r={r}")
    half = r // LANES_PER_BYTE
    sat = np.uint8(SATURATION)
    lo = jnp.minimum(regs[..., :half].astype(jnp.uint8), sat)
    hi = jnp.minimum(regs[..., half:].astype(jnp.uint8), sat)
    return (lo | (hi << np.uint8(LANE_BITS))).astype(jnp.uint8)


def unpack_rows(packed: jax.Array) -> jax.Array:
    """Unpack ``uint8[..., r/2]`` packed rows back to ``uint8[..., r]``.

    Exact inverse of :func:`pack_rows` on the packed domain:
    ``pack_rows(unpack_rows(x)) == x`` bit-for-bit for every byte panel.
    """
    p = packed.astype(jnp.uint8)
    return jnp.concatenate([p & _LO, p >> np.uint8(LANE_BITS)], axis=-1)


def max_rows(a: jax.Array, b: jax.Array) -> jax.Array:
    """Nibble-wise max of two packed panels (the packed merge operator).

    Byte-wise ``jnp.maximum`` is WRONG on packed bytes (0x10 vs 0x01
    must merge to 0x11, not 0x10); each 4-bit lane maxes independently.
    """
    lo = jnp.maximum(a & _LO, b & _LO)
    hi = jnp.maximum(a >> np.uint8(LANE_BITS), b >> np.uint8(LANE_BITS))
    return (lo | (hi << np.uint8(LANE_BITS))).astype(jnp.uint8)


def merge_rows(a: jax.Array, b: jax.Array, layout: str = "byte") -> jax.Array:
    """Layout-aware HLL merge: byte-wise or nibble-wise register max."""
    if layout == "packed":
        return max_rows(a, b)
    return jnp.maximum(a, b)


def scatter_max_rows(regs: jax.Array, dst: jax.Array, rows: jax.Array,
                     layout: str = "byte") -> jax.Array:
    """Layout-aware ``regs.at[dst].max(rows)`` (row scatter-merge).

    The packed form runs two independent scatter-maxes over the nibble
    planes and recombines — equivalent to nibble-wise max accumulation,
    which a single byte-wise ``.at[].max`` is not.
    """
    if layout != "packed":
        return regs.at[dst].max(rows)
    shift = np.uint8(LANE_BITS)
    lo = (regs & _LO).at[dst].max(rows & _LO)
    hi = (regs >> shift).at[dst].max(rows >> shift)
    return (lo | (hi << shift)).astype(jnp.uint8)


def to_layout(rows: jax.Array, src: str, dst: str) -> jax.Array:
    """Convert a register panel between layouts (identity when equal).

    ``byte -> packed`` saturates (see :func:`pack_rows`); ``packed ->
    byte`` is exact. Used by ``engine.load``/``merge`` when the caller's
    layout differs from the panel's recorded one.
    """
    validate_layout(src)
    validate_layout(dst)
    if src == dst:
        return rows
    if src == "byte":
        return pack_rows(rows)
    return unpack_rows(rows)
