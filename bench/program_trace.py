"""Readers of the program's own spans and counters (``repro.utils.trace``).

The per-layer metrics in ``bench/metrics/`` that read the program's records
share these. Each takes the records whose start lies in the run's window
and divides by the passes in the window. Each returns None where there is
no pass in the window, where the program has no recorder (a checkout
without ``repro.utils.trace``), or where no record of that name is there.
"""
from __future__ import annotations

from collections import defaultdict


def _records(run, to_end: bool = False):
    """The program's records that start in the window (with ``to_end``,
    from the window's start on); None without a recorder."""
    try:
        from repro.utils import trace
    except ImportError:
        return None
    w0, w1 = run.window
    return trace.records(w0, float("inf") if to_end else w1)


def _passes(run) -> int:
    return len(run.passes_in_window())


def span_per_pass(run, name: str, scale: float = 1.0):
    """Seconds of the spans ``name`` per pass, times ``scale``."""
    n = _passes(run)
    recs = _records(run) if n else None
    spans = [r for r in recs or () if r.name == name and r.value is None]
    if not spans:
        return None
    return scale * sum(r.t1 - r.t0 for r in spans) / n


def count_per_pass(run, name: str, scale: float = 1.0):
    """Sum of the counter ``name`` per pass, times ``scale``."""
    n = _passes(run)
    recs = _records(run) if n else None
    events = [r for r in recs or () if r.name == name and r.value is not None]
    if not events:
        return None
    return scale * sum(r.value for r in events) / n


def _covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def unspanned_per_pass(run, root: str, scale: float = 1.0):
    """Time inside the spans ``root`` that starts in the window and that no
    span below it on the same thread names, per pass, times ``scale``.

    A span with children names only what its children cover; the rest of
    it counts here. So this is the time covered by no leaf span: the sum
    of the self times of ``root`` and of every span between it and the
    leaves. Work handed to another thread (the chunk staging) is not
    counted as covering.
    """
    n = _passes(run)
    recs = _records(run, to_end=True) if n else None
    if not recs:
        return None
    w1 = run.window[1]
    spans = [r for r in recs if r.value is None]
    by_id = {r.id: r for r in spans}
    children = defaultdict(list)
    for r in spans:
        parent = by_id.get(r.parent)
        if parent is not None and parent.thread == r.thread:
            children[r.parent].append(r)
    roots = [r for r in spans if r.name == root and r.t0 <= w1]
    if not roots:
        return None
    total = 0.0
    for r in roots:
        leaves, todo = [], list(children[r.id])
        while todo:
            s = todo.pop()
            if children[s.id]:
                todo.extend(children[s.id])
            else:
                leaves.append((s.t0, s.t1))
        total += (r.t1 - r.t0) - _covered(leaves, r.t0, r.t1)
    return scale * total / n
