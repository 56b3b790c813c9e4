"""The plain reference: the paper's copy score and decision, Eqs. (2)-(8).

Written from the paper (Li et al., *Scaling up Copy Detection*, ICDE 2015,
Section II-A) in float64 NumPy and independent of the program: nothing here
imports its scoring, engine, kernel or index code, and nothing takes an
array the program made. For a source S1 and a source S2 that both claim
item D:

* same value v with truth probability P = P(D.v):
  C->(D) = ln(1 - s + s * Pr(Phi(S2)) / Pr_ind),
  Pr(Phi(S2)) = P A2 + (1 - P)(1 - A2),
  Pr_ind = P A1 A2 + (1 - P)(1 - A1)(1 - A2) / n;
* different values: C->(D) = ln(1 - s).

C->(S1, S2) sums these over the shared items, and the pair copies iff
ln(alpha / beta) + ln(e^C-> + e^C<-) >= 0, beta = 1 - 2 alpha. The paper's
INDEX gives the same decisions (its Proposition 3.4: pairs it skips can
never reach the threshold), so the reference decides by the full sum.

With ``dtype`` the same arithmetic runs in a lower precision (bfloat16 for
the check's control): every input, term and sum is rounded to it.
"""
from __future__ import annotations

import numpy as np


def row_scores(q_values, q_p, q_acc, c_values, c_acc, model,
               dtype=np.float64):
    """Scores of one query row against every row of ``c_values``.

    Returns ``(c_fwd, c_bwd, z, magnitude)``, each of shape (S,) and
    float64: C->(q, c) (q copies c), C->(c, q), the decision log-odds z
    (copying iff z >= 0), and the sum of the absolute per-item terms, which
    bounds the rounding of a float32 sum of the same terms.
    """
    f = np.dtype(dtype).type
    s, n = f(model["s"]), f(model["n"])
    alpha = f(model["alpha"])
    one = f(1)
    items = np.nonzero(np.asarray(q_values) >= 0)[0]
    qv = np.asarray(q_values)[items]
    p = np.asarray(q_p)[items][None, :].astype(dtype)
    cv = np.asarray(c_values)[:, items]
    shared = cv >= 0
    same = shared & (cv == qv[None, :])
    a_q = f(q_acc)
    a_c = np.asarray(c_acc)[:, None].astype(dtype)
    pr_ind = p * a_q * a_c + (one - p) * (one - a_q) * (one - a_c) / n
    f_fwd = np.log(one - s + s * (p * a_c + (one - p) * (one - a_c)) / pr_ind)
    f_bwd = np.log(one - s + s * (p * a_q + (one - p) * (one - a_q)) / pr_ind)
    ln1ms = np.log(one - s)
    differ = (shared & ~same).sum(axis=1).astype(dtype)
    zero = f(0)
    c_fwd = np.where(same, f_fwd, zero).sum(axis=1, dtype=dtype) + differ * ln1ms
    c_bwd = np.where(same, f_bwd, zero).sum(axis=1, dtype=dtype) + differ * ln1ms
    z = np.log(alpha / (one - f(2) * alpha)) + np.logaddexp(c_fwd, c_bwd)
    magnitude = (np.abs(np.where(same, f_fwd, zero)).astype(np.float64)
                 + np.abs(np.where(same, f_bwd, zero)).astype(np.float64)
                 ).sum(axis=1) + 2 * differ.astype(np.float64) * abs(
                     float(ln1ms))
    return (c_fwd.astype(np.float64), c_bwd.astype(np.float64),
            z.astype(np.float64), magnitude)


def decisions(z):
    """Copying iff the posterior of independence is at most one half."""
    return np.asarray(z) >= 0.0
