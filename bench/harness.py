"""The benchmark harness: one cell, one run, one result line.

Everything specific to a cell is found by name from ``BENCHMARK.json``:

* the cell (``workloads`` entry) names its configuration and its traffic;
* a configuration is the JSON file its ``configs`` entry names;
* a traffic mix is ``bench/traffic/<traffic>.json``; its ``"driver"``
  key names the module ``bench/drivers/<driver>.py`` that runs it;
* the limits of the cell's correctness comparison are
  ``bench/limits/<workload>.json``;
* a per-layer metric is read by ``bench/metrics/<reader>.py``, where
  ``<reader>`` is the metric's name up to its first ``.`` (one reader
  serves ``device_idle_pct.ingest`` and ``device_idle_pct.job``); its
  ``read(record)`` returns the metric's value or ``None``.

A driver's ``run(ctx)`` sets the cell up, calls ``ctx.open_window()``
when the timed window starts, drives the window, reads
``ctx.memory_peak()`` once the window has closed, then runs the plain
reference and returns a record (see :class:`Context`). The harness turns
the record into the result line.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager

__all__ = ["Context", "load_spec", "cell", "metric_reader", "run_cell",
           "main"]

#: the checkout's root: the directory that holds BENCHMARK.json
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` of the checkout at ``root``."""
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel), encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(spec: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found from its name.

    Returns ``{"workload", "config", "traffic", "driver", "limits",
    "end_to_end", "per_layer"}``: the workload entry, the configuration
    and traffic files' contents, the driver's path, the limits, and the
    metric entries the cell reports with ``--trace 0`` and ``--trace 1``.
    """
    work = next((w for w in spec["workloads"] if w["name"] == workload),
                None)
    if work is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in spec["configs"] if c["name"] == work["config"])
    config = _json(root, conf_entry["file"])
    traffic = _json(root, os.path.join("bench", "traffic",
                                       work["traffic"] + ".json"))
    driver = os.path.join(root, "bench", "drivers",
                          traffic["driver"] + ".py")
    limits = _json(root, os.path.join("bench", "limits", workload + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"workload": work, "config": config, "traffic": traffic,
            "driver": driver, "limits": limits, "end_to_end": e2e,
            "per_layer": layer}


def metric_reader(root: str, name: str) -> str:
    """The path of the reader of per-layer metric ``name``."""
    return os.path.join(root, "bench", "metrics", name.split(".")[0] + ".py")


def peaks(root: str, kind: str) -> dict:
    """The row of ``bench/peaks.json`` for a ``device_kind``; unknown: error."""
    table = _json(root, os.path.join("bench", "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def log(msg: str) -> None:
    """Progress on stderr (stdout's last line is the result)."""
    print(msg, file=sys.stderr, flush=True)


class Context:
    """What a driver gets: the cell, the run's arguments and the clocks.

    Attributes a driver reads: ``config``, ``traffic``, ``seed``,
    ``seconds``, ``trace`` (bool), ``control`` (bool: put the
    reference's control in the program's place), ``devices``.
    """

    def __init__(self, info: dict, seed: int, seconds: float, trace: bool,
                 control: bool, t_start: float, devices: list,
                 trace_dir: str | None):
        self.config = info["config"]
        self.traffic = info["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.devices = devices
        self.t_start = t_start
        self.t_window = None
        self._trace_dir = trace_dir
        self.trace_file = None
        self._peak = None

    def open_window(self, t0: float) -> None:
        """Mark the start of the timed window (``time.perf_counter``)."""
        self.t_window = t0

    @staticmethod
    def settle() -> None:
        """End set-up: collect its garbage and freeze what survives.

        Set-up leaves millions of long-lived objects (traced and compiled
        programs among them); frozen, they are no longer rescanned by the
        collector's full passes, which otherwise stop every thread of
        the process for up to seconds inside the window.
        """
        import gc
        gc.collect()
        gc.freeze()

    @property
    def setup_s(self) -> float:
        """Seconds from process start to the window's start."""
        return self.t_window - self.t_start

    def memory_peak(self) -> int:
        """Peak device bytes on the fullest chip; read once, after the window."""
        if self._peak is None:
            vals = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in self.devices]
            self._peak = int(max(vals)) if vals else 0
        return self._peak

    @contextmanager
    def traced(self):
        """Profile the enclosed stretch when the run traces; else nothing.

        The stretch is wrapped in the host span ``bench.trace``, which the
        trace reduction takes as the traced window.
        """
        if not self.trace:
            yield
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.trace"):
                yield
        finally:
            jax.profiler.stop_trace()
            from bench import trace as btrace
            self.trace_file = btrace.find_trace(self._trace_dir)


def _configure_jax(root: str) -> str:
    """Persistent compile cache at a fixed path; returns the directory."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def _devices(chips: int, allow_cpu: bool) -> list:
    import jax
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def _checks(values: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit; True when all are within."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and v <= limit
        ok &= good
        out[name] = {"value": v, "limit": limit}
    return out, ok


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t_start: float | None = None,
             allow_cpu: bool = False, control: bool = False) -> dict:
    """Run one cell once; returns the result object (see the contract)."""
    t_start = time.perf_counter() if t_start is None else t_start
    info = cell(load_spec(root), workload, root)
    _configure_jax(root)
    devices = _devices(int(info["workload"]["chips"]), allow_cpu)
    if not allow_cpu:
        peaks(root, devices[0].device_kind)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        ctx = Context(info, seed, seconds, trace, control, t_start, devices,
                      trace_dir)
        driver = load_module(info["driver"], "bench_driver_" +
                             info["traffic"]["driver"])
        rec = driver.run(ctx)
        reduced = None
        if ctx.trace_file is not None:
            from bench import trace as btrace
            reduced = btrace.reduce_trace(ctx.trace_file)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec["trace"] = reduced
    rec["setup_s"] = ctx.setup_s
    checks, correct = _checks(rec["checks"], info["limits"])
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.memory_peak()}
    metrics = {}
    if trace:
        for m in info["per_layer"]:
            value = load_module(metric_reader(root, m["name"]),
                                "bench_metric_" + m["name"].replace(
                                    ".", "_")).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        values = dict(rec["e2e"], setup_s=ctx.setup_s)
        for m in info["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    result["_record"] = rec
    return result


def main(argv=None, t_start: float | None = None) -> int:
    """``run.py``'s entry point; returns the exit code."""
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference's control in the program's "
                         "place (a check of the comparison, not a run)")
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start,
                       control=args.control)
    except NoDevice as e:
        log(f"bench: {e}")
        return 3
    rec = res.pop("_record")
    for key in ("lateness", "counts", "timings"):
        if key in rec:
            log(f"{key}: {json.dumps(rec[key])}")
    if rec.get("trace"):
        log(f"idle by span: {json.dumps(rec['trace']['idle_by_span'])}")
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0
