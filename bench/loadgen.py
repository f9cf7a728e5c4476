"""Open-loop load from a fixed schedule, issued by a fixed pool of clients.

A copy of the open loop of ``repro.serve.loadgen`` with three changes:

* every request's latency runs from the instant it was *due* in the
  schedule, not from when a thread got round to it, so a stall of the
  server or of the generator lengthens the latency of every request
  queued behind it;
* requests are issued by a fixed pool of client threads, not one new
  thread per request;
* the generator reports how late it ran: for each request, the time from
  its due instant to the instant it was issued.

The schedule (arrival offsets and request payloads) is made in full
before the window opens, from the seed, so the same seed gives the same
work. Requests whose issue raises count as failed.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

__all__ = ["ZipfSampler", "Outcome", "affine_permutation", "poisson_arrivals",
           "run_open_loop", "latency_summary"]


class ZipfSampler:
    """Ranks ``0..n-1`` drawn with weight ``(rank + 1) ** -s``.

    Exact inverse-CDF sampling over the discrete distribution.
    """

    def __init__(self, n: int, s: float):
        if n < 1 or s <= 0:
            raise ValueError(f"need n >= 1 and s > 0, got n={n}, s={s}")
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
        cdf = np.cumsum(w)
        self._cdf = cdf / cdf[-1]

    def sample(self, rng: np.random.Generator, size) -> np.ndarray:
        """Draw ranks (int64) of the given shape; rank 0 is the hottest."""
        u = rng.random(size)
        idx = np.searchsorted(self._cdf, u, side="right")
        return np.minimum(idx, len(self._cdf) - 1).astype(np.int64)


def affine_permutation(rng: np.random.Generator, n: int):
    """A seeded bijection of ``[0, n)``: ``rank -> (a * rank + b) % n``."""
    while True:
        a = int(rng.integers(1, max(n, 2)))
        if np.gcd(a, n) == 1:
            break
    b = int(rng.integers(0, max(n, 1)))
    return lambda ranks: (a * np.asarray(ranks, np.int64) + b) % n


def poisson_arrivals(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Arrival offsets (s) of a Poisson process at ``rate`` over ``seconds``."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0, got {rate}")
    expected = int(rate * seconds * 1.2 + 64)
    gaps = rng.exponential(1.0 / rate, size=expected)
    due = np.cumsum(gaps)
    while due[-1] < seconds:
        more = np.cumsum(rng.exponential(1.0 / rate, size=expected))
        due = np.concatenate([due, due[-1] + more])
    return due[due < seconds]


@dataclass
class Outcome:
    """What happened to each scheduled request (host clock, seconds)."""

    due: np.ndarray        # absolute due instants
    issued: np.ndarray     # when a client called the server
    done: np.ndarray       # when the answer (or error) came back
    ok: np.ndarray         # bool: answered without raising
    results: list          # answer per request (None where it failed)
    errors: list           # (index, repr) of failed requests
    before: np.ndarray     # the caller's ``before()`` read at issue
    after: np.ndarray      # the caller's ``after()`` read at the answer

    @property
    def latency(self) -> np.ndarray:
        """Seconds from due to answer; +inf for a failed request."""
        return np.where(self.ok, self.done - self.due, np.inf)

    @property
    def lateness(self) -> np.ndarray:
        """Seconds from due to issue: how late the generator ran."""
        return self.issued - self.due


def run_open_loop(issue, payloads: list, offsets: np.ndarray, t0: float,
                  clients: int, span=None, before=None, after=None,
                  wait_seconds: float = 60.0) -> Outcome:
    """Issue ``issue(payload)`` for each payload at ``t0 + offset``.

    ``clients`` threads take requests in schedule order; a thread that
    takes a request before it is due sleeps until then. ``span`` is an
    optional context-manager factory wrapped around each call (a trace
    annotation). ``before()`` and ``after()``, if given, are read just
    before each issue and just after each answer (the driver passes
    counters of acknowledged and started ingest blocks).
    Waits at most ``wait_seconds`` past the last due instant for the
    answers; a request still open then counts as failed.
    """
    count = len(payloads)
    due = t0 + np.asarray(offsets, np.float64)
    issued = np.full(count, np.nan)
    done = np.full(count, np.nan)
    ok = np.zeros(count, bool)
    results: list = [None] * count
    errors: list = []
    mark_before = np.zeros(count, np.int64)
    mark_after = np.zeros(count, np.int64)
    lock = threading.Lock()
    nxt = [0]

    def client():
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= count:
                return
            delay = due[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            if before is not None:
                mark_before[i] = before()
            issued[i] = time.perf_counter()
            try:
                if span is None:
                    out = issue(payloads[i])
                else:
                    with span():
                        out = issue(payloads[i])
            except Exception as e:  # noqa: BLE001 — a failed request
                done[i] = time.perf_counter()
                with lock:
                    errors.append((i, repr(e)))
            else:
                done[i] = time.perf_counter()
                results[i] = out
                ok[i] = True
            if after is not None:
                mark_after[i] = after()

    threads = [threading.Thread(target=client, name=f"bench-client-{c}",
                                daemon=True) for c in range(clients)]
    for th in threads:
        th.start()
    deadline = (due[-1] if count else t0) + wait_seconds
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.perf_counter()))
    with lock:
        nxt[0] = count            # clients still waiting take nothing new
        open_now = [i for i in range(count) if np.isnan(done[i])]
        for i in open_now:
            errors.append((i, "no answer within the wait"))
    ok[open_now] = False
    return Outcome(due=due, issued=issued, done=done, ok=ok,
                   results=results, errors=errors, before=mark_before,
                   after=mark_after)


def latency_summary(out: Outcome) -> dict:
    """Counts, latency percentiles and generator lateness, in ms."""
    lat = out.latency
    late = out.lateness[~np.isnan(out.issued)]
    finite = np.isfinite(lat)

    def pct(x, q):
        """Percentile in ms; None where it falls on a failed request."""
        if not x.size:
            return None
        if np.isfinite(x).all():
            return float(np.percentile(x, q) * 1e3)
        v = float(np.percentile(x, q, method="inverted_cdf"))
        return v * 1e3 if np.isfinite(v) else None

    return {
        "requests": int(len(lat)),
        "failed": int((~out.ok).sum()),
        "p50_ms": pct(lat[finite], 50),
        "p95_ms": pct(lat, 95),
        "p99_ms": pct(lat, 99),
        "late_p50_ms": pct(late, 50),
        "late_p95_ms": pct(late, 95),
        "late_max_ms": float(late.max() * 1e3) if late.size else None,
    }
