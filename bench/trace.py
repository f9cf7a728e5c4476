"""Reduce a ``jax.profiler`` trace to device busy/idle time and its causes.

Reads an ``.xplane.pb`` file with ``jax.profiler.ProfileData`` only; it
never touches a device. From the trace it takes:

* the traced window: the host span named :data:`WINDOW_SPAN` (the
  benchmark wraps the traced stretch in it), or else the extent of all
  events;
* the device's busy time: the union of the intervals of the operations
  on each TPU core's ``XLA Ops`` line, clipped to the window and averaged
  over the devices;
* the device operations that took the most time, summed by program and
  op (an op nested in another, as a loop body in its ``while``, counts
  in both, so these need not add up to the busy time);
* the longest idle gaps of the first device, each labelled with the
  benchmark's own host spans (names starting with ``bench.``) that were
  open at the gap's midpoint.
"""
from __future__ import annotations

import glob
import os

import numpy as np

__all__ = ["WINDOW_SPAN", "find_trace", "reduce_trace", "busy_union"]

#: host span that bounds the traced stretch
WINDOW_SPAN = "bench.trace"
#: the line of a device plane that holds one event per executed op
OPS_LINE = "XLA Ops"
#: the line that holds one event per executed program
MODULES_LINE = "XLA Modules"
#: host spans of the benchmark's own, used to label idle gaps
SPAN_PREFIX = "bench."
TOP = 10


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def busy_union(starts: np.ndarray, ends: np.ndarray) -> list:
    """Merged ``[start, end)`` intervals covering the given ones."""
    if len(starts) == 0:
        return []
    order = np.argsort(starts, kind="stable")
    out = []
    cur_s, cur_e = float(starts[order[0]]), float(ends[order[0]])
    for i in order[1:]:
        s, e = float(starts[i]), float(ends[i])
        if s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            out.append((cur_s, cur_e))
            cur_s, cur_e = s, e
    out.append((cur_s, cur_e))
    return out


def _drop_layouts(text: str) -> str:
    depth, out = 0, []
    for ch in text:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            out.append(ch)
    return "".join(out)


def op_label(name: str) -> str:
    """A short label of an ``XLA Ops`` event: ``fusion.3 u8[8,1024] fusion``.

    The event's name is the op's HLO text; keep its name, its result type
    without the layouts, and the op kind.
    """
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:100]
    words = _drop_layouts(rest).split("(", 1)[0].split()
    if not words:
        return head.lstrip("%")
    typ = " ".join(words[:-1])
    return f"{head.lstrip('%')} {typ[:60]} {words[-1]}".replace("  ", " ")


def _module_names(plane):
    """(starts, ends, names) of the programs on a device plane."""
    line = next((x for x in plane.lines if x.name == MODULES_LINE), None)
    if line is None:
        return np.zeros(0), np.zeros(0), []
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                   e.name.split("(")[0]) for e in line.events))
    return (np.asarray([e[0] for e in evs], np.float64),
            np.asarray([e[1] for e in evs], np.float64), [e[2] for e in evs])


def _planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def _device_planes(planes) -> list:
    return [p for p in planes if p.name.startswith("/device:TPU:")
            and any(line.name == OPS_LINE for line in p.lines)]


def reduce_trace(path: str) -> dict:
    """Busy/idle seconds, top device ops and labelled idle gaps.

    Returns ``{"busy_s", "window_s", "devices", "device_ops",
    "idle_gaps", "idle_by_span"}``; times in seconds. ``busy_s`` is 0
    when the trace holds no device plane (a CPU run).
    """
    planes = list(_planes(path))
    spans = []          # (name, start, end) of the benchmark's host spans
    window = None
    extent = [np.inf, -np.inf]
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                extent = [min(extent[0], s), max(extent[1], e)]
                if ev.name == WINDOW_SPAN:
                    window = (s, e) if window is None else (
                        min(window[0], s), max(window[1], e))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, s, e))
    devices = _device_planes(planes)
    op_time: dict = {}
    busy = []
    gaps = []
    for k, plane in enumerate(devices):
        line = next(x for x in plane.lines if x.name == OPS_LINE)
        names, starts, ends = [], [], []
        for ev in line.events:
            names.append(ev.name)
            starts.append(ev.start_ns)
            ends.append(ev.start_ns + ev.duration_ns)
        starts = np.asarray(starts, np.float64)
        ends = np.asarray(ends, np.float64)
        if window is None and len(starts):
            extent = [min(extent[0], starts.min()), max(extent[1], ends.max())]
        lo, hi = window if window is not None else extent
        keep = (ends > lo) & (starts < hi)
        starts_c = np.clip(starts[keep], lo, hi)
        ends_c = np.clip(ends[keep], lo, hi)
        m_start, m_end, m_name = _module_names(plane)
        for name, s, e in zip(np.asarray(names, object)[keep], starts_c,
                              ends_c):
            j = np.searchsorted(m_start, s, side="right") - 1
            module = m_name[j] if j >= 0 and s < m_end[j] else "?"
            label = f"{module}: {op_label(name)}"
            op_time[label] = op_time.get(label, 0.0) + (e - s)
        merged = busy_union(starts_c, ends_c)
        busy.append(sum(e - s for s, e in merged))
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    lo, hi = window if window is not None else extent
    window_ns = max(hi - lo, 0.0) if np.isfinite(hi - lo) else 0.0
    mids = np.asarray([0.5 * (a + b) for a, b in gaps], np.float64)
    open_names = [[] for _ in gaps]
    for name in sorted({n for n, _, _ in spans}):
        st = np.sort([a for n, a, _ in spans if n == name])
        en = np.sort([b for n, _, b in spans if n == name])
        is_open = (np.searchsorted(st, mids, side="right")
                   - np.searchsorted(en, mids, side="right")) > 0
        for i in np.flatnonzero(is_open):
            open_names[i].append(name)
    labelled = []
    by_span: dict = {}
    for (s, e), names_open in zip(gaps, open_names):
        label = "+".join(names_open) if names_open else "no bench span"
        labelled.append((label, (e - s) * 1e-9))
        by_span[label] = by_span.get(label, 0.0) + (e - s) * 1e-9
    labelled.sort(key=lambda x: -x[1])
    ops = sorted(op_time.items(), key=lambda x: -x[1])[:TOP]
    n_dev = max(len(devices), 1)
    return {
        "busy_s": float(sum(busy) / n_dev) * 1e-9,
        "window_s": window_ns * 1e-9,
        "devices": len(devices),
        "device_ops": [[n, float(t) * 1e-9 / n_dev] for n, t in ops],
        "idle_gaps": [[n, float(t)] for n, t in labelled[:TOP]],
        "idle_by_span": {k: v for k, v in sorted(by_span.items(),
                                                 key=lambda x: -x[1])},
    }
