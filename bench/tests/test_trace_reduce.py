"""The trace reduction on small traces with hand-counted answers."""
import json
import shutil
from pathlib import Path

import pytest

import harness
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _planes():
    host = ("/host:CPU", {"python": [
        ("bench.window", 1000.0, 11000.0),
        ("bench.pass", 1500.0, 10500.0),
        ("bench.prologue", 1500.0, 4000.0),
        ("bench.scan", 4000.0, 9000.0),
    ]})
    dev = ("/device:TPU:0", {"XLA Ops": [
        ("fusion.1", 500.0, 1500.0),          # half inside the window
        ("%copyscore_fused.1 = (f32[256,256]) custom-call(s8[256,3072])",
         4500.0, 5500.0),
        ("copyscore_fused.1", 5000.0, 6000.0),  # overlaps the previous op
        ("add", 9500.0, 10000.0),
    ], "Steps": [("0", 0.0, 20000.0)]})
    return [host, dev]


def test_busy_idle_kernel_and_gaps_by_hand():
    red = trace_reduce.reduce_planes(_planes(), {"copyscore": "copyscore"})
    assert red["window_s"] == pytest.approx(10000e-9)
    # busy: [1000,1500] + [4500,6000] + [9500,10000] = 2500 ns
    assert red["busy_s"] == pytest.approx(2500e-9)
    assert red["kernel_s"]["copyscore"] == pytest.approx(2000e-9)
    # ops go by their HLO instruction name, not the whole HLO text
    assert red["device_ops"][0] == ["copyscore_fused.1", pytest.approx(2e-6)]
    gaps = dict(red["idle_gaps"])
    # idle [1500,4500]: midpoint 3000 in prologue; [6000,9500]: 7750 in scan;
    # [10000,11000]: 10500 in pass (ends exactly there) -> the pass
    assert gaps["prologue"] == pytest.approx(3000e-9)
    assert gaps["scan"] == pytest.approx(3500e-9)
    assert gaps["pass"] == pytest.approx(1000e-9)


VOTE = '%vote.1 = f32[8,8] custom-call(), custom_call_target="tpu_custom_call"'


def test_copyscore_yields_the_ops_a_kernel_of_the_cell_matches():
    """Copyscore's ``tpu_custom_call`` would take every custom call; it is
    the catch-all, so a kernel of the cell's own keeps its ops."""
    host, dev = _planes()
    dev[1]["XLA Ops"].append((VOTE, 7000.0, 8000.0))
    red = trace_reduce.reduce_planes(
        [host, dev], {"vote": "vote", "copyscore": harness.KERNEL_PATTERN},
        catch_all="copyscore")
    assert red["kernel_s"] == {"vote": pytest.approx(1000e-9),
                               "copyscore": pytest.approx(2000e-9)}
    assert red["kernel_names"]["vote"] == [VOTE]


def test_an_op_two_kernels_match_is_an_error():
    host, dev = _planes()
    dev[1]["XLA Ops"].append((VOTE, 7000.0, 8000.0))
    with pytest.raises(ValueError, match="vote.1"):
        trace_reduce.reduce_planes(
            [host, dev], {"vote": "vote", "custom": "custom-call",
                          "copyscore": harness.KERNEL_PATTERN},
            catch_all="copyscore")


BOOK_FULL = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]
    if w["config"] == "book_full"]
COPYSCORE_OP = ('%copyscore_fused_pallas.1 = (f32[256,256]{1,0}, '
                'f32[256,256]{1,0}) custom-call(s8[256,3072]{1,0} %p.1), '
                'custom_call_target="tpu_custom_call"')
ALLOC_OP = '%alloc.2 = s8[3328,512]{1,0} custom-call(), custom_call_target="AllocateBuffer"'


@pytest.mark.parametrize("name", BOOK_FULL)
def test_a_kernel_file_of_another_cell_leaves_copyscore_alone(name, tmp_path):
    """A kernel file added for another cell, whose pattern matches every
    custom call, changes neither copyscore's ops nor its time in a
    Book-full trace."""
    host = ("/host:CPU", {"python": [("bench.window", 0.0, 10000.0)]})
    dev = ("/device:TPU:0", {"XLA Ops": [
        (COPYSCORE_OP, 1000.0, 3000.0), (ALLOC_OP, 3000.0, 3500.0),
        (COPYSCORE_OP, 4000.0, 6500.0), ("%fusion.4 = f32[8] fusion()",
                                         7000.0, 9000.0)]})

    def reduced(root):
        pats = harness.kernel_patterns(harness.load_cell(name, root))
        return pats, trace_reduce.reduce_planes(
            [host, dev], pats, catch_all="copyscore")

    root = tmp_path / "root"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", root)
    (root / "bench" / "kernels").mkdir(exist_ok=True)
    (root / "bench" / "kernels" / "greedy.json").write_text(json.dumps(
        {"pattern": "custom-call|copyscore", "why": "matches every custom call",
         "workloads": ["other.cell"]}))
    pats, before = reduced(harness.ROOT)
    pats_after, after = reduced(root)
    assert pats_after == pats == {"copyscore": harness.KERNEL_PATTERN}
    assert after["kernel_names"] == before["kernel_names"] == {
        "copyscore": [COPYSCORE_OP]}
    assert after["kernel_s"] == before["kernel_s"] == {
        "copyscore": pytest.approx(4500e-9)}


def test_a_trace_without_window_or_device_is_refused():
    host, dev = _planes()
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([dev])
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([host])


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e (two jitted calls and a sleep inside
    the window), reduced to the planes the reduction reads."""
    rec = json.loads((DATA / "tpu_trace_small.json").read_text())
    planes = [(p[0], {k: [tuple(e) for e in v] for k, v in p[1].items()})
              for p in rec["planes"]]
    red = trace_reduce.reduce_planes(planes, {"mm": rec["kernel_pattern"]})
    want = rec["expected"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    # the expected busy time was counted on a 1 us grid
    assert red["busy_s"] == pytest.approx(want["busy_s"],
                                          abs=2e-6 * want["n_ops"])
    assert red["kernel_s"]["mm"] == pytest.approx(want["kernel_s"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert all(" = " not in name for name, _ in red["device_ops"])
