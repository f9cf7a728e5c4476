"""The four-chip sharded service cell, on four virtual CPU devices.

A tiny copy of the benchmark (``make_tiny_root``, scale 9) runs the cell
``svc4-s24p10.mixed-zipf-ie`` in a child process that sees four CPU
devices (the device count is fixed before JAX is imported, so it cannot
be this process). Sound runs read ``correct``; the control and two
faults of an exchange between chips read not correct:

* one owner's share of every routed block left out (an exchange lost
  between chips): the registers differ;
* every served answer gathered from the neighbouring shard's rows.

The three readers of the cell's own metrics, and the trace reduction that
feeds ``collective_ms``, are checked on records and planes built here.
"""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import harness
from bench.tests.conftest import REPO

CELL = "svc4-s24p10.mixed-zipf-ie"

_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
from bench.tests.conftest import make_tiny_root, run_tiny
from repro.engine import sharded
from repro.graph import stream

root = make_tiny_root(sys.argv[2])
cell = sys.argv[3]
out = {}


def run(name, **kw):
    res = run_tiny(root, cell, **kw)
    rec = res.pop("_record")
    out[name] = {"correct": res["correct"], "checks": res["checks"],
                 "metrics": res["metrics"], "device": res["device"],
                 "failed": res["failed"], "shards": rec["counts"].get(
                     "memory") and len(rec["counts"]["memory"])}


run("sound")
run("traced", trace=True)
run("control", control=True)

whole = stream.bucket_by_owner


def lossy(edges, n_pad, shards):
    per = whole(edges, n_pad, shards)
    per[1] = per[1][:0]
    return per


sharded.gstream.bucket_by_owner = lossy
run("lost_exchange")
sharded.gstream.bucket_by_owner = whole

E = sharded.ShardedEngine
real = {k: getattr(E, k) for k in ("_union_presplit",
                                   "_intersection_presplit",
                                   "_query_batch_presplit")}


def neighbour(self, ids):
    return None if ids is None else [
        (x + self.v_loc) % self.n_pad for x in ids] if isinstance(
        ids, list) else (ids + self.v_loc) % self.n_pad


E._union_presplit = lambda self, sets: real["_union_presplit"](
    self, neighbour(self, sets))
E._intersection_presplit = lambda self, arr, *a: real[
    "_intersection_presplit"](self, neighbour(self, arr), *a)
E._query_batch_presplit = lambda self, sets, arr, *a: real[
    "_query_batch_presplit"](self, neighbour(self, sets),
                             neighbour(self, arr), *a)
run("neighbour_rows")
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT, REPO,
         str(tmp_path_factory.mktemp("shard4") / "checkout"), CELL],
        env=env, capture_output=True, text=True, timeout=1200, cwd=REPO)
    line = [x for x in res.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, res.stdout[-3000:] + "\n" + res.stderr[-6000:]
    return json.loads(line[-1][len("RESULT "):])


def _failed(run) -> list:
    return [k for k, c in run["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]


def test_sound_run_is_correct_on_four_devices(runs):
    run = runs["sound"]
    assert run["correct"] and run["failed"] == 0, run["checks"]
    assert run["device"]["count"] == 4 and run["shards"] == 4
    assert {"setup_s", "query_p95_ms", "ingest_edges_per_s"} <= set(
        run["metrics"])


def test_traced_run_reports_the_sharded_metrics(runs):
    run = runs["traced"]
    assert run["correct"], run["checks"]
    assert {"route_host_ms.ingest", "route_pad_pct.ingest"} <= set(
        run["metrics"])
    assert run["metrics"]["route_host_ms.ingest"]["value"] > 0
    assert 0 <= run["metrics"]["route_pad_pct.ingest"]["value"] < 100


def test_control_is_not_correct(runs):
    assert not runs["control"]["correct"], runs["control"]["checks"]


def test_lost_exchange_between_chips(runs):
    run = runs["lost_exchange"]
    assert not run["correct"] and "reg_mismatch" in _failed(run)


def test_answer_from_the_neighbouring_shard(runs):
    run = runs["neighbour_rows"]
    assert not run["correct"]
    assert {"union_gap", "inter_gap"} & set(_failed(run)), run["checks"]


def _reader(name: str):
    return harness.load_module(harness.metric_reader(REPO, name),
                               "bench_metric_" + name.replace(".", "_"))


def test_route_host_ms():
    read = _reader("route_host_ms.ingest").read
    spans = {"ds.engine.ingest.route": {"count": 8, "total_ms": 20.0}}
    assert read({"server_stats": {"spans": spans}}) == 2.5
    assert read({"server_stats": {"spans": {
        "ds.engine.ingest": {"count": 3, "total_ms": 9.0}}}}) is None
    assert read({"server_stats": {"spans": {"ds.engine.ingest.route": {
        "count": 0, "total_ms": 0.0}}}}) is None
    assert read({}) is None


def test_route_pad_pct():
    read = _reader("route_pad_pct.ingest").read
    events = {"route_slots": 300, "route_padded": 100}
    assert read({"server_stats": {"events": events}}) == 25.0
    assert read({"server_stats": {"events": {"route_slots": 5}}}) is None
    assert read({"server_stats": {"spans": {}}}) is None
    assert read({}) is None


def test_collective_ms():
    read = _reader("collective_ms.serve").read
    coll = {"collective_s": 0.003, "devices": 4, "segments": 12,
            "window_s": 3.0, "ops": []}
    assert read({"collectives": coll}) == pytest.approx(0.25)
    assert read({"collectives": dict(coll, segments=0)}) is None
    assert read({"collectives": dict(coll, devices=0)}) is None
    assert read({"trace": {"busy_s": 1.0}}) is None


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def test_collectives_reduce_constructed_planes():
    from bench import collectives
    host = _plane("/host:CPU", python=[
        _ev("bench.trace", 1000, 9000), _ev("ds.serve.segment", 1500, 10),
        _ev("ds.serve.segment", 5000, 10), _ev("ds.serve.segment", 20000, 5)])
    ar = "%all-reduce.3 = (u32[2,8]{1,0}) all-reduce(%b.1), channel_id=2"
    gte = ("%get-tuple-element.1 = u32[2,8]{1,0} "
           "get-tuple-element(%all-reduce.3)")
    dev0 = _plane("/device:TPU:0", XLA_Ops=[
        _ev(ar, 2000, 400), _ev(gte, 2400, 50),
        _ev("%fusion.2 = u8[8,1024]{1,0} fusion(%p.1)", 3000, 500),
        _ev(ar, 9800, 600)],                       # clipped at 10,000
        Async_XLA_Ops=[_ev("all-gather-start.1", 2200, 400)])
    dev1 = _plane("/device:TPU:1", XLA_Ops=[_ev(ar, 2000, 100)])
    got = collectives.reduce_planes([host, dev0, dev1])
    # dev0: [2000, 2600) from the all-reduce and the overlapping async
    # all-gather, plus [9800, 10000); dev1: 100 ns; mean over 2 devices
    assert got["collective_s"] == pytest.approx((800 + 100) / 2 * 1e-9)
    assert got["devices"] == 2 and got["segments"] == 2
    assert got["window_s"] == pytest.approx(9e-6)
    assert not collectives.is_collective(gte)
    assert collectives.is_collective(ar)


def test_collectives_on_a_one_chip_trace():
    from bench import collectives
    data = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_trace.xplane.pb")
    got = collectives.reduce_collectives(data)
    assert got["devices"] == 1 and got["collective_s"] == 0.0
    assert got["segments"] == 0       # recorded before the ds. spans
    assert _reader("collective_ms.serve").read({"collectives": got}) is None
