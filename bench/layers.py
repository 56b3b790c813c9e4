"""Helpers the per-layer metric readers in ``bench/metrics/`` share.

Each reader is ``read(run) -> float | None`` over a ``harness.Run``; None
means nothing to read (a hook that has gone, no pass in the window, no
trace), and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

import numpy as np

def hooked(run, *spans) -> bool:
    """False when a span's hook has gone missing from the program."""
    from harness import HOOKS

    attrs = {span: attr for _, _, attr, span in HOOKS}
    missing = " ".join(run.spans.missing)
    return not any(f".{attrs[s]}" in missing for s in spans)


def seconds(run, name: str, exclude_under=()) -> float:
    """Total seconds of the spans ``name`` that start in the window."""
    return sum(s.seconds for s in run.spans.in_window(name, run.window,
                                                      exclude_under))


def per_pass(run, names, exclude_under=(), scale: float = 1.0):
    """Mean per pass in the window of the spans ``names``, times ``scale``."""
    if not hooked(run, "pass", *names):
        return None
    n = len(run.passes_in_window())
    if not n:
        return None
    return scale * sum(seconds(run, x, exclude_under) for x in names) / n


def idle_pct(run):
    """Percent of the traced window in which no operation ran on the
    device."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def compiles(run):
    """Compilations that began and ended inside the window."""
    return float(len(run.compiles))


def roofline(run):
    """The copyscore kernel's share of its roofline over the window."""
    import roofline as rl

    if run.trace is None or run.peak is None:
        return None
    ops = nbytes = 0.0
    for p in run.passes_in_window():
        st = p["stats"]
        if "chunk_tiles_run" not in st:
            return None
        o, b = rl.copyscore_work(st)
        ops, nbytes = ops + o, nbytes + b
    got = rl.roofline_share(ops, nbytes, run.trace["kernel_s"]["copyscore"],
                            run.peak)
    if got is None:
        return None
    share, bound = got
    print(f"[bench] copyscore: {ops:.4g} int8 ops, {nbytes:.4g} bytes in "
          f"{run.trace['kernel_s']['copyscore']:.4f} s of kernel time, "
          f"{bound}-bound, {share:.3f} % of the roofline", flush=True)
    return share


def stat_mean(run, key: str):
    """Mean per pass in the window of one ``last_stats`` counter."""
    vals = [p["stats"][key] for p in run.passes_in_window()
            if key in p["stats"]]
    return float(np.mean(vals)) if vals else None
