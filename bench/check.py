"""The comparison that decides ``correct``: served answers against the
plain reference (``bench/reference.py``).

For every compared pair of sources, with ``magnitude`` the sum of the
absolute per-item terms of both directions (the scale of a float32 sum's
rounding):

* ``near_score_rel_gap`` is, over pairs the program scored (posterior of
  independence below 1) whose reference log-odds lie within
  ``exact_band`` of the threshold, the largest gap between the served and
  the reference C-> or log-odds, over the pair's magnitude. The
  configuration's guarantee makes those scores exact up to float32
  rounding, which grows with the magnitude: a pair of 10,000 shared items
  whose terms cancel to a log-odds near 0 carries a float32 error of
  several hundredths;
* ``decision_mismatch`` counts pairs whose served decision differs from the
  reference's. A pair whose reference log-odds lie within the rounding that
  ``near_score_rel_gap``'s limit allows (``ROUND_ABS + rel_tol *
  magnitude``) is left out and counted in ``rounding_band``: a sum within
  that tolerance may fall on either side there.

The control puts the reference itself, computed in bfloat16 (the precision
below the float32 the configurations state for scores), in the program's
place: ``compare_rows(..., control=CONTROL_DTYPE)`` compares its answers
for the same rows instead of the served ones.
"""
from __future__ import annotations

import sys

import numpy as np

from reference import decisions, row_scores

try:
    from ml_dtypes import bfloat16 as CONTROL_DTYPE
except ImportError:                             # pragma: no cover
    from jax.numpy import bfloat16 as CONTROL_DTYPE

#: Absolute floor of the rounding band around the threshold.
ROUND_ABS = 1e-4


class Tally:
    """Accumulates the compared numbers over rows; ``rel_tol`` is the
    relative rounding a sum may carry (``near_score_rel_gap``'s limit)."""

    def __init__(self, rel_tol: float):
        self.rel_tol = float(rel_tol)
        self.pairs = 0
        self.decision_mismatch = 0
        self.rounding_band = 0
        self.near_pairs = 0
        self.near_score_gap = 0.0          # absolute, for the log only
        self.near_score_rel_gap = 0.0

    def add_row(self, ref, copying, c_fwd=None, pr=None, skip=None,
                exact_band: float = 1.0) -> None:
        """Compare one served row with its reference ``row_scores``."""
        ref_fwd, _, z, mag = ref
        keep = np.ones(len(z), bool)
        if skip is not None:
            keep[skip] = False
        band = np.abs(z) < ROUND_ABS + self.rel_tol * mag
        cmp = keep & ~band
        self.pairs += int(keep.sum())
        self.rounding_band += int((keep & band).sum())
        self.decision_mismatch += int(
            (np.asarray(copying, bool)[cmp] != decisions(z)[cmp]).sum())
        if pr is None:
            return
        pr = np.asarray(pr, np.float64)
        near = keep & (pr < 1.0) & (np.abs(z) < exact_band)
        if not near.any():
            return
        pr_n = np.clip(pr[near], 1e-300, 1 - 1e-16)
        z_served = np.log((1 - pr_n) / pr_n)
        gap = np.abs(z_served - z[near])
        if c_fwd is not None:
            gap = np.maximum(gap, np.abs(np.asarray(c_fwd, np.float64)[near]
                                         - ref_fwd[near]))
        self.near_pairs += int(near.sum())
        self.near_score_gap = max(self.near_score_gap, float(gap.max()))
        self.near_score_rel_gap = max(
            self.near_score_rel_gap,
            float((gap / np.maximum(mag[near], 1.0)).max()))


def compare_row(tally: Tally, q_values, q_p, q_acc, c_values, c_acc, copying,
                model, exact_band, c_fwd=None, pr=None, skip=None,
                control=None) -> None:
    """Score one query row against ``c_values`` and compare the served
    answers (``copying``, optional ``c_fwd``/``pr``) with the reference's;
    with ``control`` (a dtype) the reference computed in that precision
    takes the served answers' place."""
    args = (q_values, q_p, q_acc, c_values, c_acc, model)
    ref = row_scores(*args)
    if control is not None:
        lo_fwd, _, lo_z, _ = row_scores(*args, dtype=control)
        copying = decisions(lo_z)
        c_fwd = None if c_fwd is None else lo_fwd
        with np.errstate(over="ignore"):
            pr = None if pr is None else 1.0 / (1.0 + np.exp(lo_z))
    tally.add_row(ref, copying, c_fwd, pr, skip=skip, exact_band=exact_band)


def compare_rows(tally: Tally, q_values, q_p, q_acc, c_values, c_p, c_acc,
                 copying, model, exact_band, c_fwd=None, pr=None,
                 skip=None, control=None) -> None:
    """``compare_row`` for each query row (one served row per query);
    ``skip[r]`` is a column left out of row r (the row itself)."""
    del c_p  # a shared value has one truth probability on both sides
    for r in range(len(q_values)):
        compare_row(tally, q_values[r], q_p[r], q_acc[r], c_values, c_acc,
                    copying[r], model, exact_band,
                    None if c_fwd is None else c_fwd[r],
                    None if pr is None else pr[r],
                    skip=None if skip is None else skip[r], control=control)


def judge(numbers: dict, limits: dict, show: bool = True
          ) -> tuple[bool, dict]:
    """Hold each number to its limit (``value <= limit``); returns
    ``(correct, {name: {"value", "limit"}})`` and, with ``show``, prints
    each pair as the last lines on standard error."""
    out = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value <= limit
        ok &= good
        out[name] = {"value": value, "limit": limit}
    for name, v in out.items() if show else ():
        print(f"check {name} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    return ok, out
