"""64-bit hashing for HLL sketches, emulated in two uint32 lanes.

The paper uses xxhash (non-cryptographic, 64-bit avalanche). JAX disables
uint64 by default (x64 mode would change weak-type promotion for the whole
framework), so we emulate a 64-bit hash as a pair of independent 32-bit
murmur3 finalizers (fmix32) with distinct seed mixing. HLL theory only
requires uniform, well-avalanched bits; fmix32 passes the usual avalanche
criteria. p+q = 64 is preserved: the bucket comes from the top p bits of the
hi lane, and rho is the leading-zero count of the remaining q = 64-p bits
(hi remainder concatenated with the full lo lane), plus one.

All functions are jit-safe and operate on uint32 arrays of any shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fmix32", "hash64", "bucket_rho", "bucket_rho32"]

_GOLD_HI = np.uint32(0x9E3779B9)  # golden-ratio odd constant (splitmix)
_GOLD_LO = np.uint32(0x85EBCA6B)


def fmix32(x: jax.Array) -> jax.Array:
    """murmur3 32-bit finalizer: full avalanche over a uint32 lane."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash64(keys: jax.Array, seed: int = 0) -> tuple[jax.Array, jax.Array]:
    """Hash integer keys to an emulated 64-bit word (hi, lo) of uint32.

    The two lanes are independent fmix32 chains with different seed mixing,
    so the concatenated 64 bits behave as a single 64-bit hash for HLL
    purposes (bucket from hi, rho window spanning both lanes).
    """
    k = keys.astype(jnp.uint32)
    # Seed mixing folds to numpy scalar literals (Python-int arithmetic,
    # wrapped mod 2^32) so kernel bodies that inline this hash never
    # close over device-array constants (Pallas rejects captured arrays).
    s_hi = np.uint32((int(seed) * 0x9E3779B9 + 0x27D4EB2F) & 0xFFFFFFFF)
    s_lo = np.uint32((int(seed) * 0x85EBCA6B + 0x165667B1) & 0xFFFFFFFF)
    hi = fmix32(k ^ s_hi)
    lo = fmix32((k + _GOLD_LO) ^ s_lo)
    # cross-mix so hi/lo are not independent of each other's low bits only
    hi = fmix32(hi + lo * _GOLD_HI)
    return hi, lo


def bucket_rho(keys: jax.Array, p: int, seed: int = 0) -> tuple[jax.Array, jax.Array]:
    """Map keys -> (bucket in [0, 2^p), rho in [1, q+1]) with q = 64 - p.

    rho is the 1-based position of the first set bit in the q-bit window
    that follows the p bucket bits; q+1 if the window is all zeros. This is
    exactly the paper's xi/rho split with p + q = 64 (Section 4).
    """
    bucket, rho = bucket_rho32(keys, p, seed)
    return bucket, rho.astype(jnp.uint8)


def bucket_rho32(keys: jax.Array, p: int,
                 seed: int = 0) -> tuple[jax.Array, jax.Array]:
    """:func:`bucket_rho` with an int32 rho: the form kernel bodies use,
    whose scalar unit has no 8-bit integers."""
    if not (1 <= p <= 31):
        raise ValueError(f"p must be in [1, 31], got {p}")
    q = 64 - p
    hi, lo = hash64(keys, seed=seed)
    bucket = (hi >> np.uint32(32 - p)).astype(jnp.int32)
    # Build the q-bit window left-aligned in a 64-bit (w_hi, w_lo) pair.
    w_hi = (hi << np.uint32(p)) | (lo >> np.uint32(32 - p))
    w_lo = lo << np.uint32(p)
    lz_hi = jax.lax.clz(w_hi)
    lz_lo = jax.lax.clz(w_lo)
    lz = jnp.where(w_hi != 0, lz_hi, np.uint32(32) + lz_lo).astype(jnp.int32)
    return bucket, jnp.minimum(lz, q) + 1
