"""Jitted public wrappers around the registered kernel implementations.

Handles padding to block multiples, the padding-index parking conventions
the kernels rely on, and impl selection through the ``kernels.registry``
(the ``impl: str`` if/else dispatch this module used to hard-code is now
data: ``ref`` and ``pallas`` are ordinary ``(family, op, impl)``
registrations — the ADS family re-registers the HLL accumulate/
propagate/estimate bodies verbatim, since k-partition ADS rows share
the register geometry, and adds the family-specific ``hip_delta`` op):

* ``impl="pallas"`` — pl.pallas_call kernels. Off-TPU they run in
  interpret mode (the TPU lowering is the target; interpret executes the
  same kernel body for correctness validation). Interpret mode is decided
  per call via ``registry.interpret_mode()``, not at import time.
* ``impl="ref"``    — the pure-jnp oracles (XLA scatter/gather lowering).

Every op takes a ``layout`` keyword ("byte" | "packed", DESIGN.md §11)
naming the register-panel representation of its ``regs`` argument. The
ref impls bridge packed panels through ``kernels.packing`` around the
byte-layout oracles; the pallas impls thread the layout into the kernel
bodies, which unpack in VMEM. Block-size arguments default to ``None``
and resolve through the ``kernels.autotune`` cache (deterministic
fallback table off-TPU).

Core modules default to the ref path on CPU; the kernels are the TPU
hot-spot replacements and the unit of the §Perf kernel iteration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hll
from repro.core.hashing import bucket_rho
from repro.core.hll import HLLConfig
from repro.kernels import autotune, packing, ref, registry, tiles
from repro.kernels.hll_accumulate import hll_accumulate as _acc_kernel
from repro.kernels.hll_propagate import hll_propagate as _prop_kernel
from repro.kernels.hll_estimate import hll_estimate_stats as _est_kernel
from repro.kernels.hip_delta import hip_delta_rows as _hip_kernel
from repro.kernels.ertl_stats import ertl_stats as _ertl_kernel
from repro.kernels.union_estimate import union_estimate_stats as _union_kernel
from repro.kernels.intersection_stats import (
    intersection_stats as _inter_kernel)

__all__ = ["accumulate", "accumulate_donated", "propagate", "estimate",
           "ertl_stats", "union_estimate", "intersection_stats", "hip_delta"]


def _pad_to(x: jax.Array, mult: int, fill) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


def _pad_rows(regs: jax.Array) -> jax.Array:
    """Pad a panel's rows to the kernels' aligned row tile (engines already
    allocate aligned tables, so this copies only for odd direct calls)."""
    return _pad_to(regs, tiles.tile_rows(regs.dtype), 0)


def _blk(op: str, name: str, value: int | None) -> int:
    """Last-resort block default for direct registered-fn calls (the
    public dispatchers resolve through the autotune cache before this)."""
    return value if value is not None else autotune.FALLBACK[op][name]


def _panel_p(regs: jax.Array, layout: str) -> int:
    """Recover the HLL precision from a panel's (layout-dependent) width."""
    r = regs.shape[1]
    if layout == "packed":
        r *= packing.LANES_PER_BYTE
    return r.bit_length() - 1


# --------------------------------------------------------------- accumulate
@registry.register("accumulate", "ref")
@registry.register("accumulate", "ref", family="ads")
def _accumulate_ref(regs, rows, keys, mask, *, cfg, layout="byte",
                    edge_block=None):
    buckets, rhos = bucket_rho(keys, cfg.p, cfg.seed)
    if mask is not None:
        rhos = jnp.where(mask, rhos, jnp.uint8(0))
        rows = jnp.where(mask, rows, 0)
    if layout == "packed":
        full = ref.hll_accumulate_ref(packing.unpack_rows(regs), rows,
                                      buckets, rhos)
        return packing.pack_rows(full)
    return ref.hll_accumulate_ref(regs, rows, buckets, rhos)


@registry.register("accumulate", "pallas")
@registry.register("accumulate", "pallas", family="ads")
def _accumulate_pallas(regs, rows, keys, mask, *, cfg, layout="byte",
                       edge_block=None):
    edge_block = _blk("accumulate", "edge_block", edge_block)
    e = rows.shape[0]
    rows = _pad_to(rows.astype(jnp.int32), edge_block, 0)
    keys = _pad_to(keys.astype(jnp.uint32), edge_block, 0)
    if mask is None:
        mask = jnp.ones((e,), bool)
    mask = _pad_to(mask, edge_block, False)
    out = _acc_kernel(_pad_rows(regs), rows, keys, mask, p=cfg.p,
                      seed=cfg.seed, layout=layout, edge_block=edge_block,
                      interpret=registry.interpret_mode())
    return out[:regs.shape[0]]


def accumulate(regs: jax.Array, rows: jax.Array, keys: jax.Array,
               cfg: HLLConfig, mask: jax.Array | None = None,
               impl: str = "pallas", edge_block: int | None = None,
               layout: str = "byte", family: str = "hll") -> jax.Array:
    """Insert keys[e] into sketch regs[rows[e]] (Algorithm 1 INSERT).

    The bucket/rho hash split happens inside the registered impl (fused
    into the kernel body for ``pallas`` — the hashed streams never round
    -trip through HBM); callers hand over raw uint32 keys plus a padding
    mask.
    """
    edge_block = autotune.resolve_block("accumulate", "edge_block",
                                        edge_block, p=cfg.p, impl=impl,
                                        layout=layout)
    fn = registry.lookup("accumulate", impl, family)
    return fn(regs, rows, keys, mask, cfg=cfg, layout=layout,
              edge_block=edge_block)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("cfg", "impl", "edge_block", "layout",
                                    "family"))
def accumulate_donated(regs: jax.Array, rows: jax.Array, keys: jax.Array,
                       mask: jax.Array, *, cfg: HLLConfig,
                       impl: str = "pallas",
                       edge_block: int | None = None,
                       layout: str = "byte",
                       family: str = "hll") -> jax.Array:
    """Donating :func:`accumulate`: the ingestion hot-path entry.

    The register panel ``regs`` is donated — XLA reuses its buffer for the
    output, so a block-ingestion loop (``regs = accumulate_donated(regs,
    ...)``) updates the panel in place instead of allocating a fresh
    n_pad*r table per block. The Pallas kernel already aliases the panel
    (``input_output_aliases={0: 0}``); donation extends the aliasing
    through the jit boundary. The caller's ``regs`` reference is consumed:
    do not reuse it after the call. One compilation is cached per
    (block shape, cfg, impl, layout) — callers pad blocks to shape buckets.
    """
    return accumulate(regs, rows, keys, cfg, mask=mask, impl=impl,
                      edge_block=edge_block, layout=layout, family=family)


# ---------------------------------------------------------------- propagate
@registry.register("propagate", "ref")
@registry.register("propagate", "ref", family="ads")
def _propagate_ref(regs, src, dst, mask, *, layout="byte", edge_block=None):
    m = jnp.ones(src.shape, bool) if mask is None else mask
    if layout == "packed":
        # gathered packed rows masked to the all-zero (empty) row, then
        # nibble-plane scatter-max — byte-wise .at[].max would drop lanes.
        rows = jnp.where(m[:, None], regs[src], jnp.uint8(0))
        return packing.scatter_max_rows(regs, dst, rows, layout="packed")
    return ref.hll_propagate_ref(regs, src, dst, m)


@registry.register("propagate", "pallas")
@registry.register("propagate", "pallas", family="ads")
def _propagate_pallas(regs, src, dst, mask, *, layout="byte",
                      edge_block=None):
    edge_block = _blk("propagate", "edge_block", edge_block)
    src = _pad_to(src.astype(jnp.int32), edge_block, 0)
    dst = _pad_to(dst.astype(jnp.int32), edge_block, 0)
    out = _prop_kernel(_pad_rows(regs), src, dst, layout=layout,
                       edge_block=edge_block,
                       interpret=registry.interpret_mode())
    return out[:regs.shape[0]]


def propagate(regs: jax.Array, src: jax.Array, dst: jax.Array,
              mask: jax.Array | None = None, impl: str = "pallas",
              edge_block: int | None = None,
              layout: str = "byte", family: str = "hll") -> jax.Array:
    """One Algorithm 2 merge pass over an edge block."""
    if mask is not None:
        src = jnp.where(mask, src, 0)
        dst = jnp.where(mask, dst, 0)  # (0,0) self-merge is a no-op
    edge_block = autotune.resolve_block("propagate", "edge_block", edge_block,
                                        p=_panel_p(regs, layout), impl=impl,
                                        layout=layout)
    fn = registry.lookup("propagate", impl, family)
    return fn(regs, src, dst, mask, layout=layout, edge_block=edge_block)


# ----------------------------------------------------------------- estimate
@registry.register("estimate", "ref")
@registry.register("estimate", "ref", family="ads")
def _estimate_stats_ref(regs, *, layout="byte", row_block=None):
    if layout == "packed":
        regs = packing.unpack_rows(regs)
    return ref.hll_estimate_ref(regs, 0.0)  # alpha unused in the stats form


@registry.register("estimate", "pallas")
@registry.register("estimate", "pallas", family="ads")
def _estimate_stats_pallas(regs, *, layout="byte", row_block=None):
    row_block = _blk("estimate", "row_block", row_block)
    n = regs.shape[0]
    padded = _pad_to(regs, row_block, 0)
    stats = _est_kernel(padded, layout=layout, row_block=row_block,
                        interpret=registry.interpret_mode())
    return stats[:n, 0], stats[:n, 1]


def estimate(regs: jax.Array, cfg: HLLConfig, impl: str = "pallas",
             row_block: int | None = None,
             layout: str = "byte", family: str = "hll") -> jax.Array:
    """Flajolet + linear-counting estimate per sketch row (uint8[N, w]).

    The fused kernels produce the (s, z) harmonic statistics; the final
    Flajolet/linear-counting combination happens here (O(N) scalar work).
    Other estimators are handled above this seam — see
    ``registry.KernelSet.estimate_rows`` for the explicit fallback. The
    combination only reads ``cfg.r``, so it serves the ADS family's
    plain (floor) estimates identically.
    """
    row_block = autotune.resolve_block("estimate", "row_block", row_block,
                                       p=cfg.p, impl=impl, layout=layout)
    s, z = registry.lookup("estimate", impl, family)(regs, layout=layout,
                                                     row_block=row_block)
    return hll._combine_flajolet(s, z, cfg)


# ----------------------------------------------------------- union_estimate
@registry.register("union_estimate", "ref")
def _union_estimate_ref(regs, ids, mask, *, layout="byte", set_block=None):
    if layout == "packed":
        regs = packing.unpack_rows(regs)
    return ref.union_estimate_ref(regs, ids, mask)


@registry.register("union_estimate", "pallas")
def _union_estimate_pallas(regs, ids, mask, *, layout="byte", set_block=None):
    set_block = _blk("union_estimate", "set_block", set_block)
    b = ids.shape[0]
    ids_p = _pad_to(ids.astype(jnp.int32), set_block, 0)
    mask_p = _pad_to(mask, set_block, False)
    stats = _union_kernel(_pad_rows(regs), ids_p, mask_p, layout=layout,
                          set_block=set_block,
                          interpret=registry.interpret_mode())
    return stats[:b, 0], stats[:b, 1]


def union_estimate(regs: jax.Array, ids: jax.Array, mask: jax.Array,
                   cfg: HLLConfig, impl: str = "pallas",
                   set_block: int | None = None,
                   layout: str = "byte", family: str = "hll") -> jax.Array:
    """Fused batched |∪ N(x)| over a padded (ids, mask) set panel.

    One pass per set row: gather member sketches, lane-wise max-merge,
    reduce to (s, z) — the merged register panel never hits HBM
    (DESIGN.md §10). The O(B) estimator combination honors
    ``cfg.estimator`` through ``hll.estimate_from_stats``; masked-out
    lanes merge the empty row, so padding can never inflate a union.
    """
    set_block = autotune.resolve_block("union_estimate", "set_block",
                                       set_block, p=cfg.p, impl=impl,
                                       layout=layout)
    s, z = registry.lookup("union_estimate", impl, family)(regs, ids, mask,
                                                           layout=layout,
                                                           set_block=set_block)
    return hll.estimate_from_stats(s, z, cfg)


# ------------------------------------------------------- intersection_stats
@registry.register("intersection_stats", "ref")
def _intersection_stats_ref(regs, pairs, q, *, layout="byte",
                            pair_block=None):
    if layout == "packed":
        regs = packing.unpack_rows(regs)
    return ref.intersection_stats_ref(regs, pairs[:, 0], pairs[:, 1], q)


@registry.register("intersection_stats", "pallas")
def _intersection_stats_pallas(regs, pairs, q, *, layout="byte",
                               pair_block=None):
    pair_block = _blk("intersection_stats", "pair_block", pair_block)
    b = pairs.shape[0]
    pairs_p = _pad_to(pairs.astype(jnp.int32), pair_block, 0)
    stats, sz = _inter_kernel(_pad_rows(regs), pairs_p, q, layout=layout,
                              pair_block=pair_block,
                              interpret=registry.interpret_mode())
    return stats[:b], sz[:b]


def intersection_stats(regs: jax.Array, pairs: jax.Array, cfg: HLLConfig,
                       impl: str = "pallas", pair_block: int | None = None,
                       layout: str = "byte",
                       family: str = "hll") -> tuple[jax.Array, jax.Array]:
    """Fused per-pair statistics for T̃(xy) over padded (B, 2) pair lanes.

    Gathers both endpoint sketches per pair and emits the Eq. 19 count
    histograms float32[B, 5, q+2] plus the harmonic (s, z) panels
    float32[B, 3, 2] for A / B / A ∪ B in one pass — the inputs of
    ``intersection.estimate_from_pair_stats`` — without materializing the
    gathered register panels (DESIGN.md §10). Padding pairs gather row 0
    (harmless; the plan masks the final estimates).
    """
    pair_block = autotune.resolve_block("intersection_stats", "pair_block",
                                        pair_block, p=cfg.p, impl=impl,
                                        layout=layout)
    fn = registry.lookup("intersection_stats", impl, family)
    return fn(regs, pairs, cfg.q, layout=layout, pair_block=pair_block)


# --------------------------------------------------------------- ertl_stats
@registry.register("ertl_stats", "ref")
def _ertl_stats_ref(a, b, q, *, layout="byte", pair_block=None):
    if layout == "packed":
        a = packing.unpack_rows(a)
        b = packing.unpack_rows(b)
    return ref.ertl_stats_ref(a, b, q)


@registry.register("ertl_stats", "pallas")
def _ertl_stats_pallas(a, b, q, *, layout="byte", pair_block=None):
    pair_block = _blk("ertl_stats", "pair_block", pair_block)
    e = a.shape[0]
    a2 = _pad_to(a, pair_block, 0)
    b2 = _pad_to(b, pair_block, 0)
    out = _ertl_kernel(a2, b2, q, layout=layout, pair_block=pair_block,
                       interpret=registry.interpret_mode())
    return out[:e]


def ertl_stats(a: jax.Array, b: jax.Array, cfg: HLLConfig,
               impl: str = "pallas", pair_block: int | None = None,
               layout: str = "byte", family: str = "hll") -> jax.Array:
    """Eq. (19) statistics for paired sketch rows uint8[E, w]."""
    pair_block = autotune.resolve_block("ertl_stats", "pair_block",
                                        pair_block, p=cfg.p, impl=impl,
                                        layout=layout)
    fn = registry.lookup("ertl_stats", impl, family)
    return fn(a, b, cfg.q, layout=layout, pair_block=pair_block)


# ---------------------------------------------------------------- hip_delta
@registry.register("hip_delta", "ref", family="ads")
def _hip_delta_ref(prev, cur, *, layout="byte", row_block=None):
    return ref.hip_delta_ref(prev, cur)


@registry.register("hip_delta", "pallas", family="ads")
def _hip_delta_pallas(prev, cur, *, layout="byte", row_block=None):
    row_block = _blk("hip_delta", "row_block", row_block)
    n = prev.shape[0]
    # padding rows are equal in both panels (no growth), contributing 0
    prev_p = _pad_to(prev, row_block, 0)
    cur_p = _pad_to(cur, row_block, 0)
    out = _hip_kernel(prev_p, cur_p, row_block=row_block,
                      interpret=registry.interpret_mode())
    return out[:n]


def hip_delta(prev: jax.Array, cur: jax.Array, impl: str = "pallas",
              row_block: int | None = None, layout: str = "byte",
              family: str = "ads") -> jax.Array:
    """Batch-HIP per-row increments between hop panels uint8[N, r].

    ``sum_j [cur_j > prev_j] * 2**prev_j`` per row (``core.ads.hip_delta``
    semantics) — the summed inverse change probabilities of every
    register a propagate pass grew. ADS-family op; byte layout only
    (packed lanes saturate and corrupt the 2**x weights, DESIGN.md §13).
    """
    if layout != "byte":
        raise ValueError(f"hip_delta requires byte layout, got {layout!r}")
    row_block = autotune.resolve_block("hip_delta", "row_block", row_block,
                                       p=_panel_p(prev, layout), impl=impl,
                                       layout=layout)
    fn = registry.lookup("hip_delta", impl, family)
    return fn(prev, cur, layout=layout, row_block=row_block)
