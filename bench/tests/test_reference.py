"""The plain reference against the engine, on a tiny graph on the CPU.

The reference imports nothing of the program; these tests are where the
two meet.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, graph500
from bench import reference as R


@pytest.fixture(scope="module")
def setup():
    from repro import engine
    from repro.core.hll import HLLConfig
    edges = graph500.generate(9, 16, 21)
    n = 1 << 9
    eng = engine.open(n, HLLConfig(p=8))
    eng.ingest(edges)
    tab = R.add_edges(R.table(n, 8), edges, 8, 0)
    return edges, n, eng, tab


def test_hash_matches_the_configured_hash():
    from repro.core.hashing import bucket_rho
    keys = jnp.arange(0, 1 << 16, dtype=jnp.uint32) * 7919
    for p in (8, 10):
        b, rho = bucket_rho(keys, p, 0)
        rb, rrho = R.bucket_rho(keys, p, 0)
        assert np.array_equal(np.asarray(b), np.asarray(rb))
        assert np.array_equal(np.asarray(rho), np.asarray(rrho))


def test_registers_bit_identical(setup):
    edges, n, eng, tab = setup
    assert int(jnp.sum(eng.regs[:n] != tab)) == 0


def test_estimates_agree(setup):
    edges, n, eng, tab = setup
    rows = np.asarray(tab)
    ref = compare.estimates(rows, 256, control=False)
    assert compare.estimate_gap(eng.degrees(), ref, 256).max() < 1e-4
    rng = np.random.default_rng(0)
    sets = rng.integers(0, n, (32, 3))
    ref = compare.union_answers(rows[sets], 256)
    assert compare.estimate_gap(eng.union_size(sets), ref, 256).max() < 1e-4


def test_intersection_ie_agrees(setup):
    edges, n, eng, tab = setup
    rows = np.asarray(tab)
    pairs = edges[:64]
    ref, union = compare.intersection_answers(rows[pairs[:, 0]],
                                              rows[pairs[:, 1]], 8, "ie")
    got = eng.intersection_size(pairs, method="ie")
    assert compare.intersection_gap(got, ref, union).max() < 1e-4


def test_intersection_mle_agrees_where_it_converges(setup):
    edges, n, eng, tab = setup
    rows = np.asarray(tab)
    pairs = edges[:256]
    ref, union = compare.intersection_answers(rows[pairs[:, 0]],
                                              rows[pairs[:, 1]], 8, "mle")
    gap = compare.intersection_gap(eng.intersection_size(pairs), ref, union)
    assert np.median(gap) < 1e-5


def test_propagate_agrees(setup):
    edges, n, eng, tab = setup
    local, _ = eng.neighborhood(3)
    panel = tab
    for t in range(3):
        if t:
            panel = R.propagate(panel, edges)
        ref = compare.estimates(np.asarray(panel), 256, control=False)
        assert compare.estimate_gap(local[t], ref, 256).max() < 1e-4


def test_control_is_lower_precision(setup):
    edges, n, eng, tab = setup
    rows = np.asarray(tab)
    ref = compare.estimates(rows, 256, control=False)
    ctl = compare.estimates(rows, 256, control=True)[0]
    assert compare.estimate_gap(ctl, ref, 256).max() > 1e-3
