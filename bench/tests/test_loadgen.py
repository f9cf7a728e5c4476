"""The open loop times from the due instant and reports its lateness."""
import threading
import time

import numpy as np

from bench import loadgen


def test_latency_counts_the_wait_behind_a_stall():
    # one client, requests due 10 ms apart, each taking 50 ms: request i
    # is issued about 40 ms * i late, and its latency includes that wait
    served = []

    def issue(x):
        time.sleep(0.05)
        served.append(x)
        return x

    offsets = np.arange(5) * 0.01
    t0 = time.perf_counter() + 0.02
    out = loadgen.run_open_loop(issue, list(range(5)), offsets, t0, 1)
    assert served == list(range(5)) and out.ok.all()
    late = out.lateness
    lat = out.latency
    assert late[0] < 0.04
    assert late[4] > 0.15                          # 4 * 40 ms behind
    assert np.all(np.diff(late) > 0.025)
    assert np.allclose(lat, late + (out.done - out.issued))
    assert lat[4] > 0.2                            # due-to-answer, not issue
    s = loadgen.latency_summary(out)
    assert s["late_max_ms"] > 150 and s["requests"] == 5


def test_pool_keeps_up_when_it_can():
    # 20 requests 5 ms apart, 20 ms each: one client falls ~0.3 s behind,
    # eight keep up (compared, so that a loaded host does not flake it)
    def run(clients):
        t0 = time.perf_counter() + 0.02
        out = loadgen.run_open_loop(lambda x: time.sleep(0.02), [0] * 20,
                                    np.arange(20) * 0.005, t0, clients)
        assert out.ok.all()
        return np.max(out.lateness)

    one, eight = run(1), run(8)
    assert one > 0.2
    assert eight < 0.3 * one
    assert threading.active_count() < 20           # no thread per request


def test_failed_requests_miss_every_limit():
    def issue(x):
        if x % 4 == 0:
            raise RuntimeError("refused")
        return x

    out = loadgen.run_open_loop(issue, list(range(8)), np.zeros(8),
                                time.perf_counter(), 2)
    assert out.ok.sum() == 6 and len(out.errors) == 2
    assert np.isinf(out.latency[[0, 4]]).all()
    s = loadgen.latency_summary(out)
    assert s["failed"] == 2 and s["p95_ms"] is None   # the tail is failed


def test_poisson_arrivals_and_zipf_are_seeded():
    a = loadgen.poisson_arrivals(np.random.default_rng(1), 200.0, 5.0)
    b = loadgen.poisson_arrivals(np.random.default_rng(1), 200.0, 5.0)
    assert np.array_equal(a, b) and a.max() < 5.0
    assert 800 < len(a) < 1200
    z = loadgen.ZipfSampler(1000, 0.99)
    r = z.sample(np.random.default_rng(2), 20000)
    assert r.min() >= 0 and r.max() < 1000
    assert (r == 0).mean() > 5 * (r == 9).mean()
    perm = loadgen.affine_permutation(np.random.default_rng(3), 1000)
    assert len(np.unique(perm(np.arange(1000)))) == 1000
