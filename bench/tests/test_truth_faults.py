"""Faults the truth-finding cell's check must catch, each made where the
program produces the answer, in a tiny CPU rehearsal."""
import numpy as np
import pytest

import tiny

CELL = "book_full_truth.truthfind"


def _flip_an_incremental_decision(monkeypatch):
    """An INCREMENTAL round's decisions altered."""
    from repro.core import engine

    orig = engine.incremental_detect

    def altered(*a, **k):
        res = orig(*a, **k)
        res.copying[:, 1] = ~res.copying[:, 1]
        return res

    monkeypatch.setattr(engine, "incremental_detect", altered)


def _flip_a_hybrid_decision(monkeypatch):
    """Round 1's HYBRID decisions altered."""
    from repro.core import engine

    orig = engine.bound_detect

    def altered(*a, **k):
        res = orig(*a, **k)
        res.copying[:, 2] = ~res.copying[:, 2]
        return res

    monkeypatch.setattr(engine, "bound_detect", altered)


def _nudge_the_vote(monkeypatch):
    """One round's value probabilities off by a float32 part in ten
    thousand."""
    from repro.core import truthfind

    orig = truthfind.vote_round

    def altered(*a, **k):
        p, acc = orig(*a, **k)
        return p * np.float32(1 - 1e-4), acc

    monkeypatch.setattr(truthfind, "vote_round", altered)


def _one_round_short(monkeypatch):
    """A run that stops a round early."""
    from repro.core import truthfind

    orig = truthfind.truth_finding

    def short(*a, **k):
        k["max_rounds"] -= 1
        return orig(*a, **k)

    monkeypatch.setattr(truthfind, "truth_finding", short)


FAULTS = [_flip_an_incremental_decision, _flip_a_hybrid_decision,
          _nudge_the_vote, _one_round_short]


@pytest.mark.parametrize("fault", FAULTS,
                         ids=[f.__name__.strip("_") for f in FAULTS])
def test_fault_is_caught(fault, monkeypatch):
    fault(monkeypatch)
    res = tiny.run_tiny(CELL)
    assert not res["correct"], res["checks"]
