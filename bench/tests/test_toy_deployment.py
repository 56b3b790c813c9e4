"""A deployment that brings only files: ``data/toy`` holds a
``BENCHMARK.json``, a configuration and a traffic mix with ``tiny``
blocks, a loop, a kernel pattern, limits and two readers. Copied to a
temporary root, it is rehearsed on the CPU through ``harness.run_cell``,
with no file of the harness changed for it."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import tiny

DATA = Path(__file__).resolve().parent / "data" / "toy"
CELL = "toy.passes"
TOY_OP = ('%toyscore.3 = f32[64,64]{1,0} custom-call(s8[64,512]{1,0} %p.1), '
          'custom_call_target="tpu_custom_call"')
COPY_OP = ('%copyscore_fused.1 = (f32[256,256]) custom-call(s8[256,3072]), '
           'custom_call_target="tpu_custom_call"')


@pytest.fixture
def root(tmp_path):
    dst = tmp_path / "toy"
    shutil.copytree(DATA, dst)
    return dst


def test_the_files_name_the_loop_the_cut_and_the_kernel(root):
    cell = tiny.tiny_cell(CELL, root)
    assert cell.root == root and cell.loop.__name__ == "ModePasses"
    assert harness.loops(root)[-1] == "mode_passes"
    assert cell.config["spec"]["n_sources"] == 60
    assert cell.config["spec"]["coverage"] == "book"
    assert cell.config["guarantees"] == {"exact_band": 1.0, "check_rows": 12}
    assert cell.traffic["warm_passes"] == 1
    assert list(harness.kernel_patterns(cell)) == ["toyscore", "copyscore"]
    assert [m["name"] for m in cell.end_to_end] == ["toy_pass_s", "setup_s"]


@pytest.mark.parametrize("kind,name", [("configs", "toy"),
                                       ("traffic", "passes")])
def test_a_file_without_a_tiny_block_is_an_error(root, kind, name):
    path = root / "bench" / kind / f"{name}.json"
    block = json.loads(path.read_text())
    del block["tiny"]
    path.write_text(json.dumps(block))
    with pytest.raises(KeyError, match="no tiny block"):
        tiny.tiny_cell(CELL, root)


def test_rehearsal_is_correct_and_its_control_is_not(root):
    from check import CONTROL_DTYPE

    res = tiny.run_tiny(CELL, root=root, control=CONTROL_DTYPE)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"toy_pass_s", "setup_s"}
    assert not res["control"]["correct"], res["control"]


def test_traced_rehearsal_reads_the_kernel_and_the_span(root, monkeypatch):
    """A CPU trace has no device plane: lay one over the run's own host
    planes, a toyscore and a copyscore custom call inside the window, and
    take the TPU v5e's peaks for the CPU's."""
    import roofline
    import trace_reduce

    real_planes, real_peaks = trace_reduce.planes_from_file, roofline.peaks

    def planes(path):
        got = real_planes(path)
        w0, w1 = next((e[1], e[2]) for name, lines in got
                      if name.startswith("/host") for evs in lines.values()
                      for e in evs if e[0] == trace_reduce.WINDOW)
        d = w1 - w0
        ops = [(TOY_OP, w0 + 0.1 * d, w0 + 0.3 * d),
               (COPY_OP, w0 + 0.5 * d, w0 + 0.6 * d)]
        return got + [("/device:TPU:0", {trace_reduce.OPS_LINE: ops})]

    runs = []
    made = harness.Run
    monkeypatch.setattr(trace_reduce, "planes_from_file", planes)
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: real_peaks("TPU v5 lite"))
    monkeypatch.setattr(harness, "Run", lambda **kw: runs.append(
        made(**kw)) or runs[-1])
    res = tiny.run_tiny(CELL, root=root, trace=True)
    assert res["correct"], res["checks"]
    red = runs[0].trace
    assert red["kernel_names"] == {"toyscore": [TOY_OP],
                                   "copyscore": [COPY_OP]}
    assert red["kernel_s"]["toyscore"] == pytest.approx(
        0.2 * red["window_s"], rel=1e-6)
    assert set(res["metrics"]) == {"detect_ms.toy", "toyscore_roofline.toy"}
    assert res["metrics"]["detect_ms.toy"]["value"] > 0
    assert 0 < res["metrics"]["toyscore_roofline.toy"]["value"] < 100


def test_unknown_loop_exits_2_without_a_result(root):
    """``bench/run.py`` in a checkout whose traffic names a loop that is
    neither built in nor a file: refused before JAX is asked for a chip."""
    for p in harness.BENCH.iterdir():
        if p.is_file():
            shutil.copy(p, root / "bench" / p.name)
    traffic = root / "bench" / "traffic" / "passes.json"
    mix = json.loads(traffic.read_text())
    mix["loop"] = "no_such_loop"
    traffic.write_text(json.dumps(mix))
    with pytest.raises(KeyError):
        harness.load_cell(CELL, root)
    out = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr
    assert "no loop 'no_such_loop'" in out.stderr
    assert not out.stdout.strip()
