"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* device busy seconds: the union of the intervals of the operations on each
  device's ``XLA Ops`` line, clipped to the traced window and averaged
  over the devices;
* kernel seconds: the summed device durations of the operations whose name
  matches a kernel's pattern; an operation that two kernels' patterns
  match is an error, unless one of the two is the catch-all kernel, which
  takes only what no other kernel matches;
* ``device_ops``: the ten operations with the most device time, by their
  HLO instruction name (a TPU trace names an event by its whole HLO text);
* ``idle_gaps``: the device's idle time inside the window, attributed to
  the innermost host span (``bench.<name>`` trace annotations) that covers
  each gap's midpoint, the ten largest.

The window is the host annotation ``bench.window``.
"""
from __future__ import annotations

import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def planes_from_file(path: str):
    """``[(plane_name, {line_name: [(name, start_ns, end_ns), ...]})]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                for ev in line.events)
        out.append((plane.name, lines))
    return out


def reduce_planes(planes, kernel_patterns: dict | None = None,
                  n_devices: int = 1, catch_all: str | None = None) -> dict:
    """The numbers above from ``planes_from_file`` output; ``catch_all``
    names the kernel of ``kernel_patterns`` that yields to the others."""
    host_spans = []
    window = None
    for name, lines in planes:
        if not name.startswith("/host"):
            continue
        for evs in lines.values():
            for ev in evs:
                if ev[0] == WINDOW:
                    window = (ev[1], ev[2])
                elif ev[0].startswith(SPAN_PREFIX):
                    host_spans.append((ev[0][len(SPAN_PREFIX):], ev[1], ev[2]))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w0, w1 = window
    devices = [(n, {OPS_LINE: _ops_line(l)}) for n, l in planes
               if n.startswith("/device:") and _ops_line(l)]
    devices = devices[:n_devices]
    if not devices:
        raise ValueError(f"trace has no device plane with operations; "
                         f"planes: {[n for n, _ in planes]}")
    patterns = {k: re.compile(p) for k, p in (kernel_patterns or {}).items()}
    busy_total = 0.0
    op_time = defaultdict(float)
    kernel_s = {k: 0.0 for k in patterns}
    kernel_names = {k: set() for k in patterns}
    gaps = defaultdict(float)
    for _, lines in devices:
        ivs = []
        for name, s, e in lines[OPS_LINE]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            op_time[_op_name(name)] += (e - s) * 1e-9
            hits = [k for k, pat in patterns.items() if pat.search(name)]
            if len(hits) > 1 and catch_all in hits:
                hits.remove(catch_all)
            if len(hits) > 1:
                raise ValueError(f"device op {name!r} matches the kernels "
                                 f"{hits}")
            for k in hits:
                kernel_s[k] += (e - s) * 1e-9
                kernel_names[k].add(name)
        busy = _union(ivs)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        prev = w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                gaps[_cover(host_spans, (prev + s) / 2)] += (s - prev) * 1e-9
            prev = max(prev, e)
    n = len(devices)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((k, v / n) for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_total / n,
            "kernel_s": {k: v / n for k, v in kernel_s.items()},
            "kernel_names": {k: sorted(v) for k, v in kernel_names.items()},
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}


def _ops_line(lines: dict) -> list:
    """A device plane's operations: its ``XLA Ops`` line, else its
    ``XLA Modules`` line (whole programs), else nothing."""
    return lines.get(OPS_LINE) or lines.get("XLA Modules") or []


def _op_name(event: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    return event.split(" = ", 1)[0].lstrip("%")


def _cover(spans, t: float) -> str:
    """Name of the shortest host span containing ``t`` ("none" if none)."""
    best, best_len = "none", None
    for name, s, e in spans:
        if s <= t <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def describe(path: str, limit: int = 3) -> list:
    """Plane and line names with a few events each, to read a trace by hand."""
    rows = []
    for name, lines in planes_from_file(path):
        for line, evs in lines.items():
            rows.append([name, line, len(evs), [e[0] for e in evs[:limit]]])
    return rows
