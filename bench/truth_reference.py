"""The plain reference of one copy-discounted vote round of truth finding.

ACCU voting (Dong, Berti-Equille, Srivastava, VLDB 2009), as Li et al.,
*Scaling up Copy Detection* (ICDE 2015, Section II) iterate it, in float64
NumPy and independent of the program: nothing here imports its code, and
it is given only the claims, the round's accuracies A and the round's copy
probabilities Pr(copy). With n false values per item and discount c:

* a source's vote weight is sigma_s = ln(n A_s / (1 - A_s));
* a claim's independence is I = prod (1 - c Pr(copy)[s, t]) over the
  co-providers t of the same value that rank above s, where t ranks above
  s iff A_t S + t > A_s S + s, computed in float32 (the model's order);
* a value's vote is the sum of sigma_s I over its providers, and its
  probability P is its softmax within the item, with the item's
  max(n - observed values, 0) unobserved false values at vote 0;
* a source's new accuracy is the mean P of its claims, clipped to
  [0.01, 0.99].

The sums run per claim over its co-providers (sparse): the work is the sum
over values of providers squared, never a sources x values grid.

With ``dtype`` the same arithmetic runs in a lower precision (bfloat16 for
the check's control): every input, term and sum is rounded to it.
"""
from __future__ import annotations

import numpy as np

#: Provider pairs formed at once (bounds the reference's memory).
PAIR_CHUNK = 1 << 23


def _segments(sorted_keys: np.ndarray):
    """Start and length of each run of equal keys."""
    start = np.flatnonzero(np.r_[True, sorted_keys[1:] != sorted_keys[:-1]])
    return start, np.diff(np.r_[start, len(sorted_keys)])


def vote(values, acc, pr_copy, model, dtype=np.float64):
    """One vote round.

    Args:
      values: (S, D) int, the claimed value of each source and item, -1
        where none.
      acc: (S,) the round's source accuracies.
      pr_copy: (S, S) the round's copy probabilities (0 for round 0).
      model: ``{"n", "c"}``.

    Returns ``(p, acc_new)``, float64: P of each claim's value, in the
    order of ``np.nonzero(values >= 0)``, and the (S,) new accuracies.
    """
    f = np.dtype(dtype).type
    values = np.asarray(values)
    S = values.shape[0]
    rows, cols = np.nonzero(values >= 0)
    vals = values[rows, cols].astype(np.int64)
    key = cols.astype(np.int64) * (int(vals.max(initial=0)) + 1) + vals
    order = np.argsort(key, kind="stable")
    g_start, g_size = _segments(key[order])
    src = rows[order]                  # claims grouped by value
    group_of = np.repeat(np.arange(len(g_start)), g_size)

    acc32 = np.asarray(acc, np.float32)
    rank = acc32 * np.float32(S) + np.arange(S, dtype=np.float32)
    a = acc32.astype(dtype)
    n, c = f(model["n"]), f(model["c"])
    sigma = np.log(n * a / (f(1) - a))
    lg = np.log1p(-np.clip(c * np.asarray(pr_copy).astype(dtype), f(0),
                           f(0.999)))

    # log-independence of each claim: its value's co-providers, in chunks
    log_i = np.zeros(len(src), dtype)
    k_all = g_size[group_of]           # a claim's provider pairs
    ends = np.cumsum(k_all)
    i0 = 0
    while i0 < len(src):
        i1 = max(int(np.searchsorted(ends, ends[i0] - k_all[i0] + PAIR_CHUNK,
                                     side="right")), i0 + 1)
        k = k_all[i0:i1]
        first = np.cumsum(k) - k
        ii = np.repeat(np.arange(i0, i1), k)
        jj = g_start[group_of[ii]] + np.arange(int(k.sum())) - np.repeat(
            first, k)
        s, t = src[ii], src[jj]
        term = np.where(rank[t] > rank[s], lg[s, t], f(0))
        log_i[i0:i1] = np.add.reduceat(term, first)
        i0 = i1

    votes = np.add.reduceat(sigma[src] * np.exp(log_i), g_start)
    i_start, i_size = _segments(cols[order][g_start])   # values by item
    top = np.maximum(np.maximum.reduceat(votes, i_start), f(0))
    ex = np.exp(votes - np.repeat(top, i_size))
    unobserved = np.maximum(n - i_size.astype(dtype), f(0))
    denom = np.add.reduceat(ex, i_start) + unobserved * np.exp(-top)
    p_value = ex / np.repeat(denom, i_size)

    p = np.empty(len(src), dtype)
    p[order] = np.repeat(p_value, g_size)
    s_start, s_size = _segments(rows)
    acc_new = np.zeros(S, dtype)
    acc_new[rows[s_start]] = np.add.reduceat(p, s_start) / s_size.astype(dtype)
    acc_new = np.clip(acc_new, f(0.01), f(0.99))
    return p.astype(np.float64), acc_new.astype(np.float64)
