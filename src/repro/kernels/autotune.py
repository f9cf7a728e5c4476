"""Deterministic autotune harness for kernel block sizes (DESIGN.md §11).

Every fused op takes one block-size knob (edge/row/set/pair block). The
right value depends on the device generation, the precision ``p`` (which
sets the register-row width) and the panel layout, so hard-coding one
number per op leaves performance on the table on real TPUs. This module
sweeps the candidate table (:data:`SWEEPS`) per op, times each candidate
on synthetic shapes, and caches the winner keyed by ``(device_kind, p,
op, impl, layout)``.

Determinism rules (tested by ``tests/test_autotune.py``):

* **Interpret mode never sweeps.** Off-TPU, timing a Python interpreter
  of the kernel body would tune noise; :func:`sweep` installs the
  :data:`FALLBACK` entry directly, so CI and tests resolve block sizes
  from a fixed table without running a single candidate.
* **Cache wins are stable.** A second :func:`sweep` on the same key
  returns the cached winner without re-driving candidates.
* **Unknown entries degrade, never raise.** :func:`tuned_params` on an
  op with no fallback/cache entry returns ``{}`` — a mid-query lookup
  miss must not take down the query path; callers keep their local
  defaults.

Resolution order for a block argument left as ``None``:
cache winner (merged over fallback) -> fallback table -> op default.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FALLBACK", "SWEEPS", "device_kind", "cache_key", "tuned_params",
           "resolve_block", "sweep", "clear_cache", "drive_count"]

#: deterministic per-op block sizes used when no swept winner exists
#: (always, in interpret mode). Edge blocks are multiples of 1024 (the
#: tile of a 1-D int32 SMEM block in XLA's layout), so the table compiles
#: for the TPU as it stands.
FALLBACK: dict[str, dict[str, int]] = {
    "accumulate": {"edge_block": 1024},
    "propagate": {"edge_block": 1024},
    "estimate": {"row_block": 256},
    "union_estimate": {"set_block": 8},
    "intersection_stats": {"pair_block": 64},
    "ertl_stats": {"pair_block": 128},
    "hip_delta": {"row_block": 256},
}

#: candidate grid per op; the sweep times each and keeps the fastest.
SWEEPS: dict[str, list[dict[str, int]]] = {
    "accumulate": [{"edge_block": b} for b in (1024, 2048, 4096)],
    "propagate": [{"edge_block": b} for b in (1024, 2048, 4096)],
    "estimate": [{"row_block": b} for b in (64, 128, 256, 512)],
    "union_estimate": [{"set_block": b} for b in (8, 16, 32)],
    "intersection_stats": [{"pair_block": b} for b in (32, 64, 128)],
    "ertl_stats": [{"pair_block": b} for b in (64, 128, 256)],
    "hip_delta": [{"row_block": b} for b in (64, 128, 256, 512)],
}

_CACHE: dict[tuple, dict[str, int]] = {}
_DRIVES = 0  # candidate timings actually executed (tests assert 0 off-TPU)


def device_kind() -> str:
    """Device model string of the default device (e.g. ``TPU v5e``)."""
    return jax.devices()[0].device_kind


def cache_key(op: str, p: int, impl: str = "pallas",
              layout: str = "byte") -> tuple:
    """The autotune cache key: ``(device_kind, p, op, impl, layout)``."""
    return (device_kind(), int(p), op, impl, layout)


def tuned_params(op: str, *, p: int, impl: str = "pallas",
                 layout: str = "byte") -> dict[str, int]:
    """Best-known block parameters for ``(op, impl, layout)`` at ``p``.

    Swept winners overlay the fallback table; an op known to neither
    returns ``{}`` (graceful degradation — callers keep their defaults).
    """
    base = dict(FALLBACK.get(op, {}))
    winner = _CACHE.get(cache_key(op, p, impl, layout))
    if winner:
        base.update(winner)
    return base


def resolve_block(op: str, name: str, value: int | None, *, p: int,
                  impl: str = "pallas", layout: str = "byte") -> int | None:
    """Resolve one block argument: an explicit ``value`` wins; ``None``
    consults :func:`tuned_params`."""
    if value is not None:
        return value
    return tuned_params(op, p=p, impl=impl, layout=layout).get(name)


def clear_cache() -> None:
    """Drop every cached winner (test isolation)."""
    _CACHE.clear()


def drive_count() -> int:
    """How many candidate timings have actually run in this process."""
    return _DRIVES


def _synthetic_inputs(op: str, p: int, layout: str, params: dict[str, int]):
    """Build a representative workload for one candidate timing."""
    from repro.core.hll import HLLConfig
    from repro.kernels import packing

    cfg = HLLConfig(p=p)
    rng = np.random.default_rng(0)
    n = 1024
    regs = jnp.zeros((n, packing.row_width(cfg.r, layout)), jnp.uint8)
    e = 4096
    rows = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    keys = jnp.asarray(rng.integers(0, 1 << 31, e), jnp.uint32)
    mask = jnp.ones((e,), bool)
    if op in ("accumulate", "propagate"):
        return cfg, (regs, rows, keys, mask)
    if op == "estimate":
        return cfg, (regs,)
    if op == "hip_delta":
        grown = jnp.asarray(
            np.maximum(np.asarray(regs),
                       rng.integers(0, 4, regs.shape).astype(np.uint8)))
        return cfg, (regs, grown)
    if op == "union_estimate":
        b, lanes = 32, 16
        ids = jnp.asarray(rng.integers(0, n, (b, lanes)), jnp.int32)
        return cfg, (regs, ids, jnp.ones((b, lanes), bool))
    # pair-structured ops
    b = 256
    pairs = jnp.asarray(rng.integers(0, n, (b, 2)), jnp.int32)
    return cfg, (regs, pairs)


def _drive(op: str, p: int, impl: str, layout: str,
           params: dict[str, int]) -> float:
    """Time one candidate (median of 3 after a warmup compile)."""
    global _DRIVES
    from repro.kernels import ops
    _DRIVES += 1
    cfg, args = _synthetic_inputs(op, p, layout, params)

    def run():
        if op == "accumulate":
            regs, rows, keys, mask = args
            out = ops.accumulate(regs, rows, keys, cfg, mask=mask, impl=impl,
                                 layout=layout, **params)
        elif op == "propagate":
            regs, rows, keys, mask = args
            out = ops.propagate(regs, rows, rows, mask=mask, impl=impl,
                                layout=layout, **params)
        elif op == "estimate":
            out = ops.estimate(args[0], cfg, impl=impl, layout=layout,
                               **params)
        elif op == "union_estimate":
            regs, ids, mask = args
            out = ops.union_estimate(regs, ids, mask, cfg, impl=impl,
                                     layout=layout, **params)
        elif op == "intersection_stats":
            regs, pairs = args
            out = ops.intersection_stats(regs, pairs, cfg, impl=impl,
                                         layout=layout, **params)[0]
        elif op == "ertl_stats":
            regs, pairs = args
            out = ops.ertl_stats(regs[pairs[:, 0]], regs[pairs[:, 1]], cfg,
                                 impl=impl, layout=layout, **params)
        elif op == "hip_delta":
            prev, cur = args
            out = ops.hip_delta(prev, cur, impl=impl, layout=layout,
                                **params)
        else:
            raise KeyError(f"no autotune driver for op {op!r}")
        return jax.block_until_ready(out)

    run()  # warmup (compile)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return sorted(times)[1]


def sweep(op: str, *, p: int, impl: str = "pallas", layout: str = "byte",
          force: bool = False) -> dict[str, int]:
    """Sweep the candidate table for one ``(op, impl, layout, p)`` cell.

    Returns the resolved parameters (see :func:`tuned_params`). The
    winner is cached under :func:`cache_key`; a repeat sweep on the same
    key is a cache hit and drives nothing. In interpret mode (off-TPU,
    ``registry.interpret_mode()``) the fallback entry is installed
    without timing anything — interpreter timings would tune noise.
    """
    from repro.kernels import registry

    key = cache_key(op, p, impl, layout)
    if key in _CACHE and not force:
        return tuned_params(op, p=p, impl=impl, layout=layout)
    candidates = SWEEPS.get(op)
    if not candidates:
        return tuned_params(op, p=p, impl=impl, layout=layout)
    if registry.interpret_mode():
        _CACHE[key] = dict(FALLBACK.get(op, {}))
        return tuned_params(op, p=p, impl=impl, layout=layout)
    timed = [(_drive(op, p, impl, layout, c), i) for i, c in
             enumerate(candidates)]
    _CACHE[key] = dict(candidates[min(timed)[1]])
    return tuned_params(op, p=p, impl=impl, layout=layout)
