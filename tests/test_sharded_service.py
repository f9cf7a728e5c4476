"""The sharded engine behind ``QueryServer``: opening, routing, serving.

* ``ShardedEngine.open`` makes its zero table on the mesh: no host table
  is built and nothing is uploaded.
* Each ingest chunk's routing is the span ``ds.engine.ingest.route``,
  and the event counters ``route_slots`` / ``route_padded`` count its
  directed slots and the padding of its ``shards x cap`` panels exactly.
* A ``QueryServer`` over a 4-shard engine on four virtual CPU devices (a
  child process: the device count is fixed before JAX is imported)
  answers union and intersection queries, interleaved with ingests,
  bit-identically to a local engine, and ends with the same registers.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import engine
from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.graph import generators as gen
from repro.serve import QueryServer

CFG = HLLConfig(p=8)


def _no_upload(*args, **kw):
    raise AssertionError("ShardedEngine.open uploaded a host array")


def test_open_makes_zeros_on_the_mesh(monkeypatch):
    monkeypatch.setattr(jax, "device_put", _no_upload)
    eng = engine.open(1000, CFG, backend="sharded", shards=1)
    monkeypatch.undo()
    regs = eng.regs
    assert regs.shape == (eng.n_pad, CFG.r) and regs.dtype == np.uint8
    assert regs.sharding.spec == P("sketch", None)
    assert not np.asarray(regs).any()


@pytest.mark.parametrize("k", [1, 3, 4, 5, 100])
def test_route_counters_for_a_known_block(k):
    edges = np.stack([np.arange(k), np.arange(k) + 1], axis=1)
    eng = engine.open(200, CFG, backend="sharded", shards=1)
    before = plans.event_counts()
    spans0 = plans.span_stats().get("ds.engine.ingest.route",
                                    {"count": 0})["count"]
    eng.ingest(edges)
    after = plans.event_counts()
    slots = after.get("route_slots", 0) - before.get("route_slots", 0)
    padded = after.get("route_padded", 0) - before.get("route_padded", 0)
    assert slots == 2 * k
    assert slots + padded == plans.bucket(2 * k)  # one shard, one panel
    assert plans.span_stats()["ds.engine.ingest.route"]["count"] \
        == spans0 + 1


def test_route_counters_count_every_chunk():
    eng = engine.open(1 << 12, CFG, backend="sharded", shards=1)
    edges = np.stack([np.arange(3000), np.arange(3000) + 7], axis=1)
    eng.INGEST_BLOCK = 1024  # three chunks: 1024, 1024, 952 edges
    before = plans.event_counts()
    eng.ingest(edges)
    after = plans.event_counts()
    got = {k: after.get(k, 0) - before.get(k, 0)
           for k in ("route_slots", "route_padded")}
    assert got == {"route_slots": 6000,
                   "route_padded": 3 * 2048 - 6000}


def test_server_stats_carry_route_events_and_span():
    edges = gen.rmat(7, 8, seed=3)
    n = int(edges.max()) + 1
    with QueryServer(engine.open(n, CFG, backend="sharded",
                                 shards=1)) as srv:
        srv.ingest(edges[:50])
        srv.reset_stats()
        srv.ingest(edges[50:80])
        st = srv.stats()
    assert st["events"]["route_slots"] == 60
    assert st["events"]["route_padded"] == plans.bucket(60) - 60
    assert st["spans"]["ds.engine.ingest.route"]["count"] == 1
    json.dumps(st)


def test_local_engine_routes_nothing():
    edges = gen.rmat(7, 8, seed=3)
    n = int(edges.max()) + 1
    with QueryServer(engine.open(n, CFG, backend="local")) as srv:
        srv.reset_stats()
        srv.ingest(edges)
        st = srv.stats()
    assert "route_slots" not in st["events"]
    assert "ds.engine.ingest.route" not in st["spans"]


_SCRIPT_4DEV = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro import engine
from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.graph import generators as gen
from repro.serve import QueryServer

out = {}
cfg = HLLConfig(p=8)

# open: zeros made on the four devices, nothing uploaded
put = jax.device_put
jax.device_put = None
eng = engine.open(512, cfg, backend="sharded")
jax.device_put = put
out["open"] = {"shards": eng.shards, "shape": list(eng.regs.shape),
               "spec": str(eng.regs.sharding.spec),
               "local": sorted({tuple(s.data.shape)
                                for s in eng.regs.addressable_shards}),
               "devices": len(eng.regs.sharding.device_set),
               "zero": not np.asarray(eng.regs).any()}

# a known block over 4 owners of 128 rows: (0,200) (1,2) (3,400) gives
# shard 0 four directed slots, shards 1 and 3 one each, shard 2 none
before = plans.event_counts()
eng.ingest(np.array([[0, 200], [1, 2], [3, 400]]))
after = plans.event_counts()
out["route"] = {k: after.get(k, 0) - before.get(k, 0)
                for k in ("route_slots", "route_padded")}

# QueryServer over 4 shards against a local engine, ingests interleaved
edges = gen.rmat(10, 8, seed=11)
n = int(edges.max()) + 1
sharded = engine.open(n, cfg, backend="sharded", shards=4)
local = engine.open(n, cfg, backend="local")
rng = np.random.default_rng(0)
mismatch = []
with QueryServer(sharded) as srv:
    for i, s in enumerate(range(0, len(edges), 1000)):
        block = edges[s:s + 1000]
        srv.ingest(block)
        local.ingest(block)
        sets = [rng.integers(0, n, rng.integers(1, 6)) for _ in range(7)]
        pairs = edges[rng.integers(0, s + len(block), 9)]
        for name, got, want in (
                ("union", srv.union_size(sets), local.union_size(sets)),
                ("ie", srv.intersection_size(pairs, method="ie"),
                 local.intersection_size(pairs, method="ie")),
                ("mle", srv.intersection_size(pairs, method="mle"),
                 local.intersection_size(pairs, method="mle"))):
            if not np.array_equal(got, want):
                mismatch.append([i, name])
    st = srv.stats()
out["mismatch"] = mismatch
out["blocks"] = i + 1
out["regs_equal"] = bool(np.array_equal(np.asarray(sharded.regs)[:n],
                                        np.asarray(local.regs)[:n]))
out["pad_rows_zero"] = not np.asarray(sharded.regs)[n:].any()
out["events"] = st["events"]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT_4DEV], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    line = [x for x in res.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, res.stdout[-3000:] + "\n" + res.stderr[-6000:]
    return json.loads(line[-1][len("RESULT "):])


def test_open_on_four_devices(four_devices):
    got = four_devices["open"]
    assert got["shards"] == 4 and got["devices"] == 4
    assert got["shape"] == [512, CFG.r]
    assert got["spec"] == str(P("sketch", None))
    assert got["local"] == [[128, CFG.r]]
    assert got["zero"]


def test_route_counters_on_four_owners(four_devices):
    # cap = bucket(4) = 8: four panels of 8 slots hold 6 real ones
    assert four_devices["route"] == {"route_slots": 6, "route_padded": 26}


def test_served_answers_on_four_shards_equal_local(four_devices):
    assert four_devices["blocks"] >= 5
    assert four_devices["mismatch"] == []
    assert four_devices["regs_equal"] and four_devices["pad_rows_zero"]
    assert four_devices["events"]["route_slots"] > 0
