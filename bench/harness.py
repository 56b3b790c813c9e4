"""The benchmark's harness: cells from files, set-up, the measured window,
spans and counters, the output check and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name:

* ``BENCHMARK.json`` (checkout root): the cells and the metrics;
* ``bench/configs/<config>.json``: a deployment (corpus spec, model,
  service settings, guarantees);
* ``bench/traffic/<traffic>.json``: a traffic mix, run by the loop its
  ``loop`` key names: ``closed`` or ``corpus`` (``drivers.DRIVERS``), or
  the one ``drivers.Driver`` subclass that ``bench/loops/<loop>.py``
  defines;
* ``bench/limits/<cell>.json``: the limits of the numbers compared;
* ``bench/metrics/<metric>.py``: a per-layer metric reader, ``read(run)``;
* ``bench/kernels/<kernel>.json``: ``{"pattern", "why", "workloads"}``,
  a regular expression over the device operations of one kernel, counted
  in the cells that ``workloads`` names; its time is
  ``run.trace["kernel_s"][<kernel>]``.

A configuration or traffic file may also carry a ``tiny`` block, the cut
that the CPU rehearsals (``bench/tests/tiny.py``) merge into it.
"""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: Program calls wrapped in spans: (module, class or None, attribute, span).
HOOKS = [
    ("repro.core.serving", None, "serve_batch", "pass"),
    ("repro.core.serving", None, "commit_rows", "index_commit"),
    ("repro.core.serving", None, "rollback_commit", "index_rollback"),
    ("repro.core.serving", None, "build_index", "build_index"),
    ("repro.core.engine", None, "build_index", "build_index"),
    ("repro.core.engine", "DetectionEngine", "_tiled_prologue", "prologue"),
    ("repro.core.engine", "DetectionEngine", "_run_tiled_scan", "scan"),
    ("repro.core.engine", "DetectionEngine", "_tiled_finalize", "finalize"),
    ("repro.core.engine", None, "rescore_pairs_exact", "rescore"),
]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: Device operations of the copyscore kernel: the Pallas kernel named after
#: its kernel function, and every TPU custom call that no kernel of the
#: cell's own claims (``kernel_patterns``). (XLA's own ``AllocateBuffer``
#: custom calls do not match.)
KERNEL_PATTERN = r"copyscore|tpu_custom_call"


def log(msg: str) -> None:
    """One diagnostic line on standard output (never the last line)."""
    print(f"[bench] {msg}", flush=True)


def sub_seed(seed: int, k: int) -> int:
    """A 63-bit seed derived from the run's seed and a stream number."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), int(k)])
    return int(ss.generate_state(2, np.uint64)[0] >> np.uint64(1))


# ---------------------------------------------------------------------------
# cells from files
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    loop: type                  # the drivers.Driver subclass that runs it
    root: Path = ROOT


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{[w['name'] for w in bench['workloads']]}")
    return cell_from(found[0], bench, root)


def cell_from(w: dict, bench: dict, root: Path = ROOT) -> Cell:
    """A cell from its workload entry (name, config, traffic, chips)."""
    name = w["name"]
    b = root / "bench"
    config = json.loads((b / "configs" / f"{w['config']}.json").read_text())
    traffic = json.loads((b / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((b / "limits" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [])
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    return Cell(name, w, config, traffic, limits, e2e, per_layer,
                loop_class(traffic["loop"], root), root)


def _module(path: Path, prefix: str):
    """The Python file ``path`` imported as a module of its own."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def loops(root: Path = ROOT) -> list:
    """The loop names a traffic file can give."""
    import drivers

    found = (root / "bench" / "loops").glob("*.py")
    return sorted(drivers.DRIVERS) + sorted(p.stem for p in found)


def loop_class(loop: str, root: Path = ROOT) -> type:
    """The driver class of the loop ``loop``: ``drivers.DRIVERS[loop]``,
    else the one ``drivers.Driver`` subclass that ``bench/loops/<loop>.py``
    defines. An unknown loop is a KeyError, as an unknown workload is."""
    import drivers

    if loop in drivers.DRIVERS:
        return drivers.DRIVERS[loop]
    path = root / "bench" / "loops" / f"{loop}.py"
    if not path.is_file():
        raise KeyError(f"no loop {loop!r}; loops: {loops(root)}")
    mod = _module(path, "bench_loop_")
    found = [c for c in vars(mod).values()
             if isinstance(c, type) and issubclass(c, drivers.Driver)
             and c.__module__ == mod.__name__]
    if len(found) != 1:
        raise KeyError(f"{path.name} defines {len(found)} drivers.Driver "
                       f"subclasses, not one")
    return found[0]


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   "bench_metric_").read


def kernel_patterns(cell: Cell) -> dict:
    """The device-operation pattern of each kernel the cell's trace counts:
    every ``bench/kernels/<kernel>.json`` whose ``workloads`` name the
    cell, and copyscore's, the catch-all (``trace_reduce.reduce_planes``)
    that yields to them."""
    pats = {}
    for p in sorted((cell.root / "bench" / "kernels").glob("*.json")):
        k = json.loads(p.read_text())
        if cell.name in k["workloads"]:
            pats[p.stem] = k["pattern"]
    pats["copyscore"] = KERNEL_PATTERN
    return pats


# ---------------------------------------------------------------------------
# spans and counters
# ---------------------------------------------------------------------------

@dataclass
class Span:
    name: str
    t0: float
    t1: float
    thread: int
    stack: tuple            # names of the enclosing spans on this thread

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Spans:
    """Host spans around program calls, kept in memory; with ``annotate``
    each is also a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``."""

    def __init__(self, annotate: bool = False):
        self.records: list = []
        self.annotate = annotate
        self.missing: list = []
        self._local = threading.local()
        self._undo: list = []
        self._lock = threading.Lock()

    @contextmanager
    def span(self, name: str):
        """Record one span around the body."""
        stack = self._local.__dict__.setdefault("stack", [])
        outer = tuple(stack)
        stack.append(name)
        ann = nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            t1 = time.perf_counter()
            stack.pop()
            with self._lock:
                self.records.append(Span(name, t0, t1, threading.get_ident(),
                                         outer))

    def wrap(self, owner, attr: str, name: str, before=None, after=None):
        """Replace ``owner.attr`` by a spanned call; ``before(args, kwargs)``
        returns a context that ``after(context, result)`` receives."""
        fn = owner.__dict__.get(attr) if isinstance(owner, type) else \
            getattr(owner, attr, None)
        if fn is None:
            self.missing.append(f"{getattr(owner, '__name__', owner)}.{attr}")
            return
        spans = self

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            ctx = before(args, kwargs) if before is not None else None
            with spans.span(name):
                result = fn(*args, **kwargs)
            if after is not None:
                after(ctx, result)
            return result

        setattr(owner, attr, spanned)
        self._undo.append((owner, attr, fn))

    def unwrap(self) -> None:
        """Put every wrapped attribute back."""
        for owner, attr, fn in reversed(self._undo):
            setattr(owner, attr, fn)
        self._undo.clear()

    def in_window(self, name: str, window, exclude_under=()) -> list:
        """Spans ``name`` that start inside ``window`` and are not nested
        in a span named in ``exclude_under``."""
        w0, w1 = window
        return [s for s in self.records
                if s.name == name and w0 <= s.t0 <= w1
                and not set(s.stack) & set(exclude_under)]


class CompileCounter:
    """Backend compilations (and loads from the persistent cache), with the
    time each was recorded, from JAX's monitoring events."""

    def __init__(self):
        self.events: list = []

    def __enter__(self):
        import jax.monitoring as mon

        def listener(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.events.append((time.perf_counter(), float(duration),
                                    str(kw.get("fun_name", ""))))

        self._listener = listener
        mon.register_event_duration_secs_listener(listener)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._listener)

    def between(self, t0: float, t1: float) -> list:
        """Compilations that ended inside [t0, t1] and began after t0."""
        return [e for e in self.events if t0 <= e[0] - e[1] and e[0] <= t1]


@dataclass
class Run:
    """What a per-layer metric reader reads."""

    cell: Cell
    spans: Spans
    window: tuple                          # (t0, t1), perf_counter seconds
    passes: list = field(default_factory=list)   # per pass: t, last_stats
    counters: dict = field(default_factory=dict)
    compiles: list = field(default_factory=list)  # inside the window
    trace: dict | None = None              # trace_reduce output
    peak: dict | None = None               # peaks.json entry

    def passes_in_window(self) -> list:
        w0, w1 = self.window
        return [p for p in self.passes if w0 <= p["t0"] <= w1]


def install_hooks(spans: Spans, on_pass_start=None, on_pass_end=None) -> None:
    """Wrap every call of ``HOOKS``; ``serve_batch`` also reports each pass
    to ``on_pass_start(args)`` / ``on_pass_end(ctx, result)``."""
    for mod_name, cls, attr, name in HOOKS:
        try:
            mod = importlib.import_module(mod_name)
        except ImportError:
            spans.missing.append(f"{mod_name}.{attr}")
            continue
        owner = getattr(mod, cls, None) if cls else mod
        if owner is None:
            spans.missing.append(f"{mod_name}.{cls}")
            continue
        if attr == "serve_batch":
            spans.wrap(owner, attr, name,
                       before=(lambda a, k: on_pass_start(a, k))
                       if on_pass_start else None,
                       after=on_pass_end)
        else:
            spans.wrap(owner, attr, name)
    for m in spans.missing:
        log(f"missing hook {m}: metrics that read it are left out")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(devices) -> dict:
    """The ``device`` block of the result line."""
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:                       # noqa: BLE001
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, control=None) -> dict:
    """Set up, measure, check; returns the result-line object. With
    ``control`` (a dtype) it also holds ``control``: the numbers compared
    for the reference in that precision in the program's place."""
    spans = Spans(annotate=trace)
    driver = cell.loop(cell, seed, spans)
    try:
        return _run(cell, driver, spans, seconds, trace, devices, t_start,
                    control)
    finally:
        spans.unwrap()


def _run(cell, driver, spans, seconds, trace, devices, t_start,
         control) -> dict:
    with CompileCounter() as compiles:
        driver.setup(seconds)
        trace_dir = None
        if trace:
            import tempfile

            import jax
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir)
        window_start = time.perf_counter()
        setup_s = window_start - t_start
        try:
            with spans.span("window"):
                driver.measure(seconds)
        finally:
            if trace:
                import jax
                jax.profiler.stop_trace()
    window = (window_start, driver.window_end)
    mem = memory_peak_bytes(devices)
    driver.release()

    run = Run(cell=cell, spans=spans, window=window, passes=driver.passes,
              counters=driver.counters,
              compiles=compiles.between(*window))
    log(f"window {window[1] - window[0]:.3f} s, set-up {setup_s:.3f} s, "
        f"{len(run.compiles)} compilations inside the window "
        f"{sorted({c[2] for c in run.compiles})}")
    for line in driver.notes():
        log(line)

    result = {"correct": False, "attempted": driver.attempted,
              "failed": driver.failed}
    metrics = {}
    breakdown = None
    if trace:
        import roofline
        run.peak = roofline.peaks(devices[0].device_kind)
        run.trace = _reduce_trace(trace_dir, len(devices),
                                  kernel_patterns(cell))
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.root)(run)
            if value is None:
                log(f"metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
    else:
        values = driver.end_to_end()
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    device = device_info(devices)
    device["memory_peak_bytes"] = mem
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    t_check = time.perf_counter()
    from check import judge
    if control is not None:
        ok, checks = judge(driver.check(control), cell.limits, show=False)
        result["control"] = {"correct": bool(ok), "checks": checks}
    numbers = driver.check()
    correct, checks = judge(numbers, cell.limits)
    correct &= driver.failed == 0
    log(f"output check took {time.perf_counter() - t_check:.1f} s")
    result.update({"correct": bool(correct), "metrics": metrics,
                   "device": device})
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _reduce_trace(trace_dir: str, n_devices: int, patterns: dict) -> dict:
    """Reduce the run's trace, with the kernels' ``patterns`` (copyscore's
    the catch-all), and delete it."""
    import glob
    import shutil

    import trace_reduce
    try:
        paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        red = trace_reduce.reduce_planes(
            trace_reduce.planes_from_file(paths[0]),
            kernel_patterns=patterns, n_devices=n_devices,
            catch_all="copyscore")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"trace: busy {red['busy_s']:.4f} s of {red['window_s']:.4f} s, "
        f"kernel ops {red['kernel_names']}")
    return red
