"""CPU rehearsals of every cell at a tiny size: the whole run, the control
and the faults a cell can have.

The control (the reference computed in bfloat16 in the program's place)
and each fault must turn ``correct`` false; the sound program must leave
it true.
"""
import json

import numpy as np
import pytest

import harness
import tiny

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_is_correct(name):
    res = tiny.run_tiny(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    from check import CONTROL_DTYPE

    res = tiny.run_tiny(name, control=CONTROL_DTYPE)
    assert res["correct"], res["checks"]
    assert not res["control"]["correct"], res["control"]


def _flip_first_decision(monkeypatch):
    """A served answer altered where it is produced."""
    from repro.core import serving

    orig = serving.serve_batch

    def altered(*a, **k):
        out = orig(*a, **k)
        for r in out:
            r.copying[0] = ~r.copying[0]
        return out

    monkeypatch.setattr(serving, "serve_batch", altered)


def _drop_half_the_batch(monkeypatch):
    """Half of the batch left out of the pass; its requests get the
    answers of the other half."""
    from repro.core import serving

    orig = serving.serve_batch

    def half(base, base_p, engine, requests, **k):
        keep = list(requests[: max(1, len(requests) // 2)])
        out = orig(base, base_p, engine, keep, **k)
        return [out[i % len(out)] for i in range(len(requests))]

    monkeypatch.setattr(serving, "serve_batch", half)


def _altered_corpus_answer(monkeypatch):
    """A decision of the corpus pass altered where it is produced."""
    from repro.core import engine

    orig = engine.DetectionEngine._tiled_finalize

    def altered(self, *a, **k):
        res = orig(self, *a, **k)
        res.copying[:, 0] = ~res.copying[:, 0]
        return res

    monkeypatch.setattr(engine.DetectionEngine, "_tiled_finalize", altered)


FAULTS = [
    ("book_full.serve", _flip_first_decision),
    ("book_full.serve", _drop_half_the_batch),
    ("book_full.corpus", _altered_corpus_answer),
]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}"
                              for n, f in FAULTS])
def test_fault_is_caught(name, fault, monkeypatch):
    fault(monkeypatch)
    res = tiny.run_tiny(name)
    assert not res["correct"], res["checks"]


def test_same_seed_same_inputs():
    import drivers

    cell = tiny.tiny_cell("book_full.serve")
    a = drivers.Driver(cell, 7, harness.Spans())
    b = drivers.Driver(cell, 7, harness.Spans())
    c = drivers.Driver(cell, 8, harness.Spans())
    assert np.array_equal(a.values, b.values)
    assert not np.array_equal(a.values, c.values)
    assert np.array_equal(a.make_rows(4, 9)[0], b.make_rows(4, 9)[0])


@pytest.mark.parametrize("budget_width,want", [(40, {40}), (None, None)])
def test_byte_budget_fixes_the_chunk_width(budget_width, want):
    """The configuration's byte budget caps the entry-chunk width below the
    width each batch's entry count gives, so every pass runs one kernel
    shape; without the cap the width follows the batch."""
    import drivers

    cell = tiny.tiny_cell("book_full.serve")
    cell.config["engine"]["n_buckets"] = 1
    if budget_width is None:
        del cell.config["engine"]["chunk_group_bytes"]
    else:
        cell.config["engine"]["chunk_group_bytes"] = 96 * budget_width
    d = drivers.Driver(cell, 11, harness.Spans())
    svc = d.service()
    widths = set()
    for j in range(3):
        reqs = [d.make_request(j * 8 + i, 50 + j * 8 + i) for i in range(8)]
        futs = [svc.submit(r) for r in reqs]
        svc.flush()
        [f.result() for f in futs]
        assert svc.engine.last_stats["tile"] == 96      # 60 + 32 rows
        widths.add(svc.engine.last_stats["chunk_width"])
    if want is None:
        assert len(widths) > 1 and min(widths) > 40, widths
    else:
        assert widths == want
