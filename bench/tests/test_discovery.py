"""A new cell, traffic mix and metric are found from files and entries alone."""
import json
import os

from bench import harness
from bench.tests.conftest import run_tiny

METRIC = '''"""Jobs finished in the window (a dummy metric of the test)."""


def read(record):
    """The job count, or None."""
    return float(record["counts"]["jobs"]) if "counts" in record else None
'''


def _add(root, rel, data):
    path = os.path.join(root, rel)
    with open(path, "w", encoding="utf-8") as f:
        if isinstance(data, str):
            f.write(data)
        else:
            json.dump(data, f)


def test_new_cell_from_files_alone(tiny_root):
    root = tiny_root
    with open(os.path.join(root, "bench/configs/g500-s20-p8-nbhd.json")) as f:
        config = json.load(f)
    config.update(name="g500-s8-p8-dummy", scale=8)
    _add(root, "bench/configs/g500-s8-p8-dummy.json", config)
    _add(root, "bench/traffic/t2-advance.json",
         {"driver": "nbhd_jobs", "base_fraction": 0.9, "advance_edges": 32,
          "t_max": 2, "check_jobs": 2, "check_vertices": 64,
          "check_hubs": 4, "trace_offset": 0.1, "trace_seconds": 0.3})
    _add(root, "bench/limits/dummy-s8.t2-advance.json",
         {"reg_mismatch": 0, "nbhd_gap": 1e-3})
    _add(root, "bench/metrics/jobs_in_window.py", METRIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "g500-s8-p8-dummy", "source": "test",
                            "file": "bench/configs/g500-s8-p8-dummy.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "dummy-s8.t2-advance",
                              "config": "g500-s8-p8-dummy",
                              "traffic": "t2-advance", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "job_s":
            m["workloads"].append("dummy-s8.t2-advance")
    spec["per_layer"].append({"name": "jobs_in_window.dummy",
                              "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "job_s",
                              "workloads": ["dummy-s8.t2-advance"]})
    _add(root, "BENCHMARK.json", spec)

    info = harness.cell(harness.load_spec(root), "dummy-s8.t2-advance", root)
    assert info["config"]["scale"] == 8
    assert info["traffic"]["t_max"] == 2
    assert [m["name"] for m in info["end_to_end"]] == ["setup_s", "job_s"]
    assert [m["name"] for m in info["per_layer"]] == ["jobs_in_window.dummy"]

    res = run_tiny(root, "dummy-s8.t2-advance", seconds=1.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "job_s"}
    assert res["metrics"]["job_s"]["value"] > 0
    res = run_tiny(root, "dummy-s8.t2-advance", seconds=1.0, trace=True)
    assert set(res["metrics"]) == {"jobs_in_window.dummy"}
    assert list(res)[-2:] == ["checks", "_record"]


def test_every_cell_is_found(tiny_root):
    spec = harness.load_spec(tiny_root)
    for w in spec["workloads"]:
        info = harness.cell(spec, w["name"], tiny_root)
        names = {m["name"] for m in info["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert info["per_layer"]
        for m in info["per_layer"]:
            assert m["moves"] in names
            assert os.path.exists(harness.metric_reader(tiny_root,
                                                         m["name"]))


def test_unknown_device_kind_is_an_error(tiny_root):
    import pytest
    assert harness.peaks(tiny_root, "TPU v5 lite")["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError):
        harness.peaks(tiny_root, "TPU v9 imaginary")
