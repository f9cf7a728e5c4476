"""The readers of the metrics that come from the program's own spans."""
import sys
import types

import pytest

from bench import harness
from bench.tests.conftest import REPO


def _reader(name: str):
    return harness.load_module(harness.metric_reader(REPO, name),
                               "bench_metric_" + name.replace(".", "_"))


def _record(**spans):
    return {"server_stats": {
        "queue_wait_ms": {"p50": 1.5, "p95": 12.25, "p99": 30.0,
                          "count": 40},
        "spans": {k.replace("_", "."): {"count": c, "total_ms": t}
                  for k, (c, t) in spans.items()}}}


SERVE = dict(ds_serve_segment=(10, 50.0), ds_serve_account=(10, 4.0),
             ds_engine_query_fetch=(12, 45.0), ds_engine_ingest=(4, 10.0))


def test_queue_wait_p95():
    read = _reader("queue_wait_p95_ms.serve").read
    assert read(_record(**SERVE)) == 12.25
    assert read({"server_stats": {"spans": {}}}) is None
    assert read({}) is None


def test_query_host_ms():
    read = _reader("query_host_ms.serve").read
    assert read(_record(**SERVE)) == pytest.approx((50.0 + 4.0 - 45.0) / 10)
    no_fetch = dict(SERVE)
    del no_fetch["ds_engine_query_fetch"]
    assert read(_record(**no_fetch)) is None
    assert read(_record(**dict(SERVE, ds_serve_segment=(0, 0.0)))) is None
    assert read({}) is None


def test_ingest_host_ms():
    read = _reader("ingest_host_ms.ingest").read
    assert read(_record(**SERVE)) == 2.5
    assert read(_record(ds_serve_segment=(1, 1.0))) is None
    assert read({"server_stats": {"queue_wait_ms": {}}}) is None


@pytest.mark.parametrize("stats, want", [
    ({"ds.engine.routing": {"count": 4, "total_ms": 3000.0}}, 750.0),
    ({"ds.engine.routing": {"count": 0, "total_ms": 0.0}}, None),
    ({"ds.engine.propagate": {"count": 2, "total_ms": 9.0}}, None),
    (None, None),                  # a program without span_stats
])
def test_routing_rebuild_ms(monkeypatch, stats, want):
    read = _reader("routing_rebuild_ms.job").read
    fake = types.SimpleNamespace()
    if stats is not None:
        fake.span_stats = lambda: stats
    monkeypatch.setitem(sys.modules, "repro.engine.plans", fake)
    assert read({}) == want
