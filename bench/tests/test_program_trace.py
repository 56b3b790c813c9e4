"""The readers of the program's own spans and counters: None with nothing
to read, and a value after a tiny CPU rehearsal of their cell."""
import json
import sys

import pytest

import harness
import tiny

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
READERS = [(m["name"], m["workloads"][0]) for m in BENCH["per_layer"]
           if m["source"] in ("program_span", "program_counter")
           and "program_trace" in (harness.ROOT / "bench" / "metrics"
                                   / f"{m['name']}.py").read_text()]
CELLS = sorted({cell for _, cell in READERS})


def test_every_program_reader_is_listed():
    """Every reader file of the program's records is a metric that
    ``BENCHMARK.json`` lists with a program source."""
    files = {p.name[:-len(".py")]
             for p in (harness.ROOT / "bench" / "metrics").glob("*.py")
             if "program_trace" in p.read_text()}
    assert files == {name for name, _ in READERS}


@pytest.mark.parametrize("metric,cell", READERS)
def test_empty_run_reads_none(metric, cell):
    run = harness.Run(cell=harness.load_cell(cell), spans=harness.Spans(),
                      window=(0.0, 1.0))
    assert harness.metric_reader(metric)(run) is None


@pytest.fixture(scope="module")
def rehearsed():
    """One tiny rehearsal per cell, with the ``Run`` its readers read."""
    runs = {}
    made = harness.Run

    def kept(**kw):
        run = runs[kw["cell"].name] = made(**kw)
        return run

    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "Run", kept)
    try:
        for name in CELLS:
            res = tiny.run_tiny(name)
            assert res["correct"], res["checks"]
    finally:
        mp.undo()
    return runs


@pytest.mark.parametrize("metric,cell", READERS)
def test_rehearsal_reads_a_value(metric, cell, rehearsed):
    value = harness.metric_reader(metric)(rehearsed[cell])
    assert value is not None and value >= 0, (metric, value)
    if not metric.startswith("unspanned"):
        assert value > 0, (metric, value)


@pytest.mark.parametrize("metric,cell", READERS)
def test_without_the_recorder_reads_none(metric, cell, rehearsed,
                                         monkeypatch):
    """A program without ``repro.utils.trace`` leaves the metric out."""
    import repro.utils

    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    monkeypatch.delattr(repro.utils, "trace", raising=False)
    assert harness.metric_reader(metric)(rehearsed[cell]) is None


def test_device_gathered_chunks_are_the_chunks_of_each_pass(rehearsed):
    """The counter sums, over a pass's chunk groups, the chunks gathered on
    the device: every chunk of the pass, ``last_stats["chunks"]``."""
    run = rehearsed["book_full.serve"]
    chunks = [p["stats"]["chunks"] for p in run.passes_in_window()]
    got = harness.metric_reader("device_gathered_chunks.serve")(run)
    assert got == pytest.approx(sum(chunks) / len(chunks))
