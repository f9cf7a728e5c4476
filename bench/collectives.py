"""Reduce a ``jax.profiler`` trace to the device time of collective ops.

Reads an ``.xplane.pb`` file with ``jax.profiler.ProfileData`` only. Over
the traced window (the host span ``bench.trace``, as in
``bench/trace.py``) it takes:

* on each TPU device plane, the union of the intervals of its collective
  ops, on the ``XLA Ops`` line and on the ``Async XLA Ops`` line (an
  asynchronous collective's start-to-done stretch), clipped to the
  window; the mean over the devices is ``collective_s``;
* those ops' time summed by op, averaged over the devices (``ops``);
* the number of the serving core's query segments (host span
  ``ds.serve.segment``) that started in the window (``segments``).

An op is a collective when its HLO name or kind holds one of
:data:`KINDS` (``all-reduce``, ``all-reduce-start``, ``all-gather-done``,
``fusion`` ops named after one, ...).
"""
from __future__ import annotations

import numpy as np

from bench import trace

__all__ = ["KINDS", "SEGMENT_SPAN", "is_collective", "reduce_planes",
           "reduce_collectives"]

#: the collective op kinds XLA emits (async forms add -start / -done)
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
#: host span of one served query segment
SEGMENT_SPAN = "ds.serve.segment"
#: device-plane lines that hold collective ops
LINES = (trace.OPS_LINE, "Async XLA Ops")
TOP = 10


def is_collective(name: str) -> bool:
    """Whether an op event (its HLO text) is a collective."""
    if " = " not in name:
        return any(k in name for k in KINDS)
    label = trace.op_label(name).split()    # its name, type, kind
    return any(k in label[0] or k in label[-1] for k in KINDS)


def reduce_planes(planes) -> dict:
    """:func:`reduce_collectives` over already-read planes.

    ``planes`` are objects with ``name`` and ``lines``; a line has
    ``name`` and ``events``; an event has ``name``, ``start_ns`` and
    ``duration_ns`` (``jax.profiler.ProfileData``'s, or stand-ins).
    """
    planes = list(planes)
    window, segment_starts = None, []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == trace.WINDOW_SPAN:
                    window = (s, e) if window is None else (
                        min(window[0], s), max(window[1], e))
                elif ev.name == SEGMENT_SPAN:
                    segment_starts.append(s)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and any(x.name == trace.OPS_LINE for x in p.lines)]
    if window is None:
        return {"collective_s": 0.0, "window_s": 0.0,
                "devices": len(devices), "segments": 0, "ops": []}
    lo, hi = window
    total, op_time = 0.0, {}
    for plane in devices:
        starts, ends = [], []
        for line in plane.lines:
            if line.name not in LINES:
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s or not is_collective(ev.name):
                    continue
                starts.append(s)
                ends.append(e)
                label = trace.op_label(ev.name)
                op_time[label] = op_time.get(label, 0.0) + (e - s)
        merged = trace.busy_union(np.asarray(starts, np.float64),
                                  np.asarray(ends, np.float64))
        total += sum(e - s for s, e in merged)
    n_dev = max(len(devices), 1)
    ops = sorted(op_time.items(), key=lambda x: -x[1])[:TOP]
    return {
        "collective_s": total / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(devices),
        "segments": int(sum(lo <= s < hi for s in segment_starts)),
        "ops": [[name, t / n_dev * 1e-9] for name, t in ops],
    }


def reduce_collectives(path: str) -> dict:
    """Collective device time, segments and top collective ops of a trace.

    Returns ``{"collective_s", "window_s", "devices", "segments",
    "ops"}``; times in seconds, ``collective_s`` the mean over the
    devices.
    """
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
