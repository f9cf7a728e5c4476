"""The device Graph500 generator: seeded, canonical, Graph500-shaped."""
import numpy as np

from bench import graph500


def test_same_seed_same_canonical_edges():
    seed = 2 ** 33 + 11           # wider than 32 bits, as run seeds may be
    a = graph500.generate(10, 16, seed)
    b = graph500.generate(10, 16, seed)
    assert np.array_equal(a, b)
    assert a.dtype == np.int32 and a.shape[1] == 2
    assert (a[:, 0] < a[:, 1]).all()                 # no self-loops, lo < hi
    key = a[:, 0].astype(np.int64) << 32 | a[:, 1]
    assert len(np.unique(key)) == len(a)              # no duplicates
    assert a.min() >= 0 and a.max() < 1 << 10


def test_seeds_differ_in_the_high_bits():
    a = graph500.generate(9, 16, 5)
    b = graph500.generate(9, 16, 5 + 2 ** 32)
    assert not np.array_equal(a, b)


def test_kronecker_degree_skew():
    e = graph500.generate(12, 16, 3)
    n = 1 << 12
    assert 0.6 * 16 * n < len(e) < 16 * n     # duplicates dropped, most kept
    deg = np.bincount(e.ravel(), minlength=n)
    assert deg.max() > 20 * deg.mean()               # heavy-tailed
