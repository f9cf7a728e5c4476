"""The trace reduction, on a small trace recorded on a TPU v5e and committed.

``data/tpu_trace.xplane.pb`` is the first 40 ms of a profile recorded on
one v5e with the harness's profiler options, while a ``QueryServer``
over the scale-22 / p=10 table took 65,536-edge ingest blocks
(``bench.ingest_block``) and union / intersection requests
(``bench.query``) from two threads; the events were cut to those 40 ms
and the ``bench.trace`` span shortened to match, and source paths made
relative to the repository, with the protobuf classes that ship with
TensorFlow.
"""
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_trace.xplane.pb")
EXPECTED = {"window_s": 0.04, "busy_s": 0.028495958,
            "top_op": "jit_accumulate_donated: fusion u8[4194304,1024] "
                      "fusion"}


def test_busy_union_merges_overlaps():
    merged = trace.busy_union(np.array([0, 5, 2, 20]), np.array([3, 8, 4, 25]))
    assert merged == [(0.0, 4.0), (5.0, 8.0), (20.0, 25.0)]
    assert trace.busy_union(np.array([]), np.array([])) == []


def test_op_label_drops_layouts():
    name = ("%fusion.1 = u8[1048576,256]{1,0:T(8,128)(4,1)} fusion(u8[8]"
            "{0} %copy.2), kind=kCustom, calls=%fused_computation.4")
    assert trace.op_label(name) == "fusion.1 u8[1048576,256] fusion"
    assert trace.op_label("while.12") == "while.12"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(DATA)


def test_window_and_busy(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["window_s"] == pytest.approx(EXPECTED["window_s"],
                                                rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)


def test_top_ops_are_labelled_with_their_program(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= trace.TOP
    assert all(":" in name and t > 0 for name, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(t <= reduced["busy_s"] for _, t in ops)
    assert ops[0][0] == EXPECTED["top_op"]


def test_idle_gaps_are_labelled_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert gaps and all(t > 0 for _, t in gaps)
    labels = {name for name, _ in gaps}
    assert labels & {"bench.ingest_block", "bench.query",
                     "bench.ingest_block+bench.query"}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(idle,
                                                                  rel=1e-6)
