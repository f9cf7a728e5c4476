"""The ``serve_mix`` cell on a sharded engine, with its collectives read.

Runs :func:`bench.drivers.serve_mix.run` unchanged (the configuration's
``sketch.backend`` opens the engine over every visible chip), then,
while the traced run's profile still exists, reduces its collective ops
with :mod:`bench.collectives` into the record's ``collectives`` (read by
``bench/metrics/collective_ms.py``). It also records each chip's peak
device bytes (``counts["memory"]``): chip 0 holds the Graph500
generator's samples in set-up besides its share of the table. Both go to
stderr with the record's ``counts``, beside the serving thread's
``worker_s``, the event counters and the span totals of the window.

``Setup``, ``_schedule`` and ``acked_edges_per_s`` are ``serve_mix``'s,
so ``bench/sweep.py`` sweeps this cell as it does the one-chip one.
"""
from __future__ import annotations

from bench import collectives
from bench.drivers import serve_mix
from bench.drivers.serve_mix import (  # noqa: F401 — for bench/sweep.py
    Setup, _schedule, acked_edges_per_s)

__all__ = ["run", "Setup", "acked_edges_per_s"]


def run(ctx) -> dict:
    """``serve_mix.run``, plus the trace's collectives and per-chip peaks."""
    rec = serve_mix.run(ctx)
    if ctx.trace_file is not None:
        rec["collectives"] = collectives.reduce_collectives(ctx.trace_file)
        rec["counts"]["collectives"] = rec["collectives"]
    stats = rec["server_stats"]
    for key in ("worker_s", "events", "spans"):
        rec["counts"][key] = stats.get(key)
    rec["counts"]["memory"] = [
        {k: (d.memory_stats() or {}).get(k)
         for k in ("peak_bytes_in_use", "bytes_in_use")}
        for d in ctx.devices]
    return rec
