"""The benchmark's copies of the generators reproduce the program's."""
import numpy as np
import pytest

import gen


@pytest.mark.parametrize("name", ["book_full", "stock_2wk"])
def test_copy_reproduces_program_generator_at_seed_0(name):
    from repro.data import claims

    mine = gen.synthetic_claims(gen.SPECS[name](0))
    theirs = claims.synthetic_claims(getattr(claims, f"{name}_spec")(0))
    assert np.array_equal(mine.values, theirs.dataset.values)
    assert np.array_equal(mine.accuracy, theirs.dataset.accuracy)
    assert mine.copies == theirs.copies
    assert np.array_equal(gen.oracle_claim_probs(mine.values),
                          claims.oracle_claim_probs(theirs))
    got = gen.synthetic_query_rows(mine.values, 12, seed=0)
    want = claims.synthetic_query_rows(theirs, 12, seed=0)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def test_seeds_deal_the_same_work_in_another_order():
    """Two runs' seeds give one corpus and one request stream in two orders
    of sources and items: the same claims per source and per item."""
    import drivers
    import harness
    import tiny

    cell = tiny.tiny_cell("book_full.serve")
    a = drivers.Driver(cell, 1, harness.Spans())
    b = drivers.Driver(cell, 2, harness.Spans())
    assert not np.array_equal(a.values, b.values)
    for axis in (0, 1):
        assert np.array_equal(np.sort((a.values >= 0).sum(axis=axis)),
                              np.sort((b.values >= 0).sum(axis=axis)))
    ra, rb = a.make_rows(4, 9)[0], b.make_rows(4, 9)[0]
    assert not np.array_equal(ra, rb)
    assert np.array_equal(np.sort((ra >= 0).sum(axis=1)),
                          np.sort((rb >= 0).sum(axis=1)))
