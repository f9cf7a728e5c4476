"""Graph500 Kronecker graph, generated on the device from a seed.

The Graph500 specification's generator: ``edgefactor << scale`` edge
samples, each placed by ``scale`` recursive choices of a quadrant of the
adjacency matrix with initiator probabilities A, B, C, D (0.57, 0.19,
0.19, 0.05), then every vertex label permuted by one random permutation.
The samples are made canonical as a user's edge stream would deliver an
undirected graph: self-loops and duplicates dropped, each edge once as
``(lo, hi)`` with ``lo < hi``. The surviving edges come back in a random
order drawn from the same seed, so any prefix of the list is a uniform
sample of the graph (the held-out parts of a cell are suffixes).

Everything runs in one jitted call on the device; only the final edge
list is copied to the host, as ``int32[m, 2]``. The same seed gives the
same edges in the same order.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["INITIATOR", "generate", "key_from_seed"]

#: Graph500 initiator probabilities (A, B, C, D).
INITIATOR = (0.57, 0.19, 0.19, 0.05)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key that depends on all 64 bits of ``seed``.

    ``jax.random.key`` keeps only the low 32 bits of a Python int when
    64-bit mode is off, so the high half is folded in separately.
    """
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _threshold(prob: float) -> np.uint32:
    """``bits > _threshold(p)`` holds with probability ``1 - p``."""
    return np.uint32(min(int(prob * 2.0 ** 32), 0xFFFFFFFF))


@partial(jax.jit, static_argnames=("scale", "edgefactor", "initiator"))
def _edges_on_device(key, *, scale: int, edgefactor: int,
                     initiator: tuple):
    """(lo, hi, keep count) with the kept edges first, in random order."""
    a, b, c, _ = initiator
    n = 1 << scale
    m = edgefactor << scale
    ab = a + b
    t_src = _threshold(ab)              # src bit 1 with probability C + D
    t_dst0 = _threshold(a / ab)         # dst bit 1 given src bit 0: B/(A+B)
    t_dst1 = _threshold(c / (1.0 - ab))  # dst bit 1 given src bit 1: D/(C+D)
    k_levels, k_perm, k_order = jax.random.split(key, 3)

    def level(i, carry):
        src, dst = carry
        bits = jax.random.bits(jax.random.fold_in(k_levels, i), (2, m),
                               jnp.uint32)
        src_bit = bits[0] > t_src
        dst_bit = jnp.where(src_bit, bits[1] > t_dst1, bits[1] > t_dst0)
        return (2 * src + src_bit.astype(jnp.int32),
                2 * dst + dst_bit.astype(jnp.int32))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = first & (lo != hi)
    order = jax.random.bits(k_order, (m,), jnp.uint32)
    order = jnp.where(keep, order >> 1, np.uint32(0xFFFFFFFF))
    _, lo, hi = jax.lax.sort((order, lo, hi), num_keys=1)
    return lo, hi, jnp.sum(keep, dtype=jnp.int32)


def generate(scale: int, edgefactor: int = 16, seed: int = 0,
             initiator: tuple = INITIATOR) -> np.ndarray:
    """Canonical undirected Graph500 edges ``int32[m, 2]`` on the host.

    ``m`` is a little under ``edgefactor << scale``: the duplicates and
    self-loops of the samples are dropped.
    """
    if not 1 <= scale <= 30:
        raise ValueError(f"scale must be in [1, 30], got {scale}")
    lo, hi, count = _edges_on_device(key_from_seed(seed), scale=int(scale),
                                     edgefactor=int(edgefactor),
                                     initiator=tuple(initiator))
    count = int(count)
    out = np.empty((count, 2), np.int32)
    out[:, 0] = np.asarray(lo)[:count]
    out[:, 1] = np.asarray(hi)[:count]
    return out
