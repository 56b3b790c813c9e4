"""Roofline arithmetic, the reference and the tally."""
import math

import numpy as np
import pytest

import check
import reference
import roofline


def test_roofline_against_a_hand_count():
    st = {"tile": 256, "chunk_width": 3128, "chunk_tiles_run": 91 * 65,
          "tiles_kept": 91}
    ops, nbytes = roofline.copyscore_work(st)
    assert ops == 2 * 256 * 256 * 3128 * 5915
    assert nbytes == 2 * 256 * 3128 * 5915 + 20 * 256 * 256 * 91
    peak = roofline.peaks("TPU v5 lite")
    share, bound = roofline.roofline_share(ops, nbytes, 0.1, peak)
    # T = 256 int8 operations per incidence byte, under the chip's
    # 393e12 / 819e9 = 480: the incidence stream bounds the kernel
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 819e9 / 0.1)
    assert roofline.roofline_share(ops, nbytes, 0.0, peak) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_reference_matches_the_paper_on_a_hand_pair():
    model = {"alpha": 0.1, "s": 0.8, "n": 50.0}
    q = np.array([0, 3, -1, 2])
    qp = np.array([0.95, 0.02, 0.0, 0.02])
    c = np.array([[0, 3, 1, 5]])
    fwd, bwd, z, mag = reference.row_scores(q, qp, 0.6, c, [0.8], model)
    def f(p, a1, a2):
        return math.log(0.2 + 0.8 * (p * a2 + (1 - p) * (1 - a2))
                        / (p * a1 * a2 + (1 - p) * (1 - a1) * (1 - a2) / 50))
    want_fwd = f(0.95, 0.6, 0.8) + f(0.02, 0.6, 0.8) + math.log(0.2)
    want_bwd = f(0.95, 0.8, 0.6) + f(0.02, 0.8, 0.6) + math.log(0.2)
    assert fwd[0] == pytest.approx(want_fwd)
    assert bwd[0] == pytest.approx(want_bwd)
    assert z[0] == pytest.approx(math.log(0.1 / 0.8)
                                 + np.logaddexp(want_fwd, want_bwd))


def test_tally_counts_mismatches_outside_the_rounding_band():
    ref = (np.array([1.0, -3.0, 0.0]), None, np.array([2.0, -4.0, 1e-9]),
           np.array([10.0, 10.0, 10.0]))
    t = check.Tally(1e-4)
    t.add_row(ref, [False, False, True], c_fwd=[1.0, -3.0, 0.0],
              pr=[0.1, 0.9, 0.5])
    assert t.decision_mismatch == 1 and t.rounding_band == 1
    ok, out = check.judge({"decision_mismatch": 1}, {"decision_mismatch": 0})
    assert not ok and out["decision_mismatch"]["limit"] == 0


def test_tally_scales_the_near_gap_by_the_magnitude():
    """A near pair's gap counts relative to the sum of its absolute terms;
    the rounding band around the threshold widens with it."""
    z = np.array([0.5, 0.5, 0.02])
    ref = (np.zeros(3), None, z, np.array([2000.0, 0.5, 1000.0]))
    pr = 1.0 / (1.0 + np.exp(z + np.array([0.04, 1e-6, -0.04])))
    t = check.Tally(1e-4)
    t.add_row(ref, z + np.array([0.04, 1e-6, -0.04]) >= 0, pr=pr)
    assert t.near_score_gap == pytest.approx(0.04, rel=1e-6)
    assert t.near_score_rel_gap == pytest.approx(0.04 / 1000, rel=1e-6)
    # the third pair flips its decision inside 1e-4 * 1000 of the threshold
    assert t.decision_mismatch == 0 and t.rounding_band == 1
