"""With the timed path broken underneath, ``correct`` comes out false.

Each test drives a whole tiny run on the CPU (the harness's look for a
chip is skipped) with one fault planted in the program, and sees the
comparison catch it. The faults a one-chip cell can have:

* a step that returns its state unchanged (an ingest that does not
  accumulate; a propagate that returns its input);
* half of the batch left out (an ingest that drops half of each block);
* an answer altered where it is produced (the server's union or
  intersection answer off by 1%).

No cell spans chips, so there is no exchange between chips to leave out.
The control (the reference one precision lower, in the program's place)
must come out false too.
"""
import numpy as np
import pytest

from bench.tests.conftest import run_tiny

SERVE = ("svc-s23p10.mixed-zipf-ie",)
NBHD = "nbhd-s20p8.t3-advance"


def _failed(res) -> list:
    return [k for k, c in res["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]


def test_sound_runs_are_correct(tiny_root):
    for w in SERVE + (NBHD,):
        res = run_tiny(tiny_root, w)
        assert res["correct"], (w, res["checks"])


@pytest.mark.parametrize("workload", SERVE + (NBHD,))
def test_control_is_not_correct(tiny_root, workload):
    res = run_tiny(tiny_root, workload, control=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", ("svc-s23p10.mixed-zipf-ie", NBHD))
def test_ingest_that_leaves_state_unchanged(tiny_root, monkeypatch,
                                            workload):
    from repro.engine.local import LocalEngine
    monkeypatch.setattr(LocalEngine, "_accumulate_block",
                        lambda self, chunk: None)
    res = run_tiny(tiny_root, workload)
    assert not res["correct"] and "reg_mismatch" in _failed(res)


@pytest.mark.parametrize("workload", ("svc-s23p10.mixed-zipf-ie", NBHD))
def test_half_of_each_block_left_out(tiny_root, monkeypatch, workload):
    from repro.engine.local import LocalEngine
    whole = LocalEngine._accumulate_block
    monkeypatch.setattr(LocalEngine, "_accumulate_block",
                        lambda self, chunk: whole(self,
                                                  chunk[: len(chunk) // 2]))
    res = run_tiny(tiny_root, workload)
    assert not res["correct"] and "reg_mismatch" in _failed(res)


def test_propagate_that_returns_its_input(tiny_root, monkeypatch):
    from repro.engine.local import LocalEngine
    monkeypatch.setattr(LocalEngine, "_propagate",
                        lambda self, regs, schedule: regs)
    res = run_tiny(tiny_root, NBHD)
    assert not res["correct"] and _failed(res) == ["nbhd_gap"]


@pytest.mark.parametrize("kind", ("union_size", "intersection_size"))
def test_altered_answer(tiny_root, monkeypatch, kind):
    from repro.serve import QueryServer
    real = getattr(QueryServer, kind)

    def altered(self, *args, **kw):
        return np.asarray(real(self, *args, **kw)) * 1.01

    monkeypatch.setattr(QueryServer, kind, altered)
    res = run_tiny(tiny_root, "svc-s23p10.mixed-zipf-ie")
    want = "union_gap" if kind == "union_size" else "inter_gap"
    assert not res["correct"] and want in _failed(res)
