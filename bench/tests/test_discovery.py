"""Cells, configurations, traffic, loops, limits and metric readers are
found by name; the harness refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_finds_its_files_and_metrics(name):
    import drivers

    cell = harness.load_cell(name)
    assert cell.traffic["loop"] in harness.loops()
    assert issubclass(cell.loop, drivers.Driver)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer and cell.limits
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_none(metric):
    cell = harness.load_cell(metric_cell(metric))
    run = harness.Run(cell=cell, spans=harness.Spans(), window=(0.0, 1.0))
    value = harness.metric_reader(metric)(run)
    assert value is None or (metric.startswith("window_compiles")
                             and value == 0)


def metric_cell(metric):
    return next(m["workloads"][0] for m in BENCH["per_layer"]
                if m["name"] == metric)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no_such.cell")


def test_every_config_file_is_named_and_used():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip().endswith("}")
