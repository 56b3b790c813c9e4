"""The span-and-counter recorder (``repro.utils.trace``) and the spans of a
served pass.

Covers the recorder (nesting per thread, parent links across the chunk
stage thread, the bound on the ring, time-window queries), the span tree
one ``DetectionService`` pass leaves, the host-to-device byte counter, and
the shared clock with a ``jax.profiler`` trace.
"""
import glob
import inspect
import os
import sys
import threading
import time
from collections import Counter, defaultdict

import numpy as np
import pytest

from repro.core import CopyConfig, devchunks
from repro.core.engine import DetectionEngine
from repro.core.pipeline import ChunkPrefetcher
from repro.core.serving import DetectRequest, DetectionService, serve_batch
from repro.core.types import ClaimsDataset
from repro.utils import trace

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0)


def _world(seed=0, n_src=48, n_items=200):
    rng = np.random.default_rng(seed)
    values = np.where(rng.random((n_src, n_items)) < 0.4,
                      rng.integers(0, 4, (n_src, n_items)),
                      -1).astype(np.int32)
    ds = ClaimsDataset(values=values,
                       accuracy=rng.uniform(0.3, 0.95,
                                            n_src).astype(np.float32))
    p = np.where(values == 0, 0.9,
                 np.where(values >= 0, 0.05, 0.0)).astype(np.float32)
    return ds, p


def _request(rid, n_items=200, q=2):
    rng = np.random.default_rng(100 + rid)
    vals = np.where(rng.random((q, n_items)) < 0.3,
                    rng.integers(0, 4, (q, n_items)), -1).astype(np.int32)
    acc = rng.uniform(0.3, 0.95, q).astype(np.float32)
    pq = np.where(vals == 0, 0.9,
                  np.where(vals >= 0, 0.05, 0.0)).astype(np.float32)
    return DetectRequest(rid=rid, values=vals, accuracy=acc, p_claim=pq)


def _service():
    ds, p = _world()
    return DetectionService(ds, p, CFG, mode="bucketed", tile=32, devices=1,
                            store_chunk_entries=64, chunk_group=1)


def _serve(svc, rids):
    futs = [svc.submit(_request(r)) for r in rids]
    svc.flush()
    return [f.result() for f in futs]


def _spans(recs):
    return [r for r in recs if r.value is None]


def _children(recs):
    """Span id -> its child spans on the same thread."""
    by_id = {r.id: r for r in _spans(recs)}
    out = defaultdict(list)
    for r in _spans(recs):
        parent = by_id.get(r.parent)
        if parent is not None and parent.thread == r.thread:
            out[r.parent].append(r)
    return out


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_spans_nest_per_thread():
    rec = trace.Recorder()
    done = threading.Event()

    def other():
        with rec.span("b.outer"):
            with rec.span("b.inner"):
                done.wait(5)

    th = threading.Thread(target=other)
    with rec.span("a.outer", rows=3) as a:
        th.start()
        with rec.span("a.inner") as inner:
            assert rec.current() is inner
            a.set(bytes=7)
        done.set()
        th.join()
    got = {r.name: r for r in rec.records()}
    assert got["a.inner"].parent == got["a.outer"].id
    assert got["a.outer"].parent is None
    assert got["b.inner"].parent == got["b.outer"].id
    assert got["b.outer"].parent is None      # not under the other thread's
    assert got["a.outer"].thread != got["b.outer"].thread
    assert got["a.outer"].attrs == {"rows": 3, "bytes": 7}
    assert got["a.outer"].t0 <= got["a.inner"].t0 <= got["a.inner"].t1 \
        <= got["a.outer"].t1
    assert rec.current() is None


def test_threads_lose_no_record():
    """Many threads recording at once under a short switch interval: every
    record lands, with a unique id and its own thread's parent."""
    rec = trace.Recorder(capacity=1 << 20)
    n_threads = 4 * (os.cpu_count() or 2)
    n_each = 300
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_each):
                with rec.span("outer"):
                    rec.count("n", 1)
                    with rec.span("inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(switch)
    recs = rec.records()
    assert len(recs) == 3 * n_threads * n_each
    assert len({r.id for r in recs}) == len(recs)
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.name == "outer":
            assert r.parent is None
        else:
            assert by_id[r.parent].name == "outer"
            assert by_id[r.parent].thread == r.thread


def test_counter_is_under_the_innermost_span():
    rec = trace.Recorder()
    with rec.span("outer") as sp:
        rec.count("bytes", 12, device=0)
    rec.count("loose", 1)
    c = {r.name: r for r in rec.records() if r.value is not None}
    assert c["bytes"].parent == sp.id and c["bytes"].value == 12
    assert c["bytes"].t0 == c["bytes"].t1
    assert c["bytes"].attrs == {"device": 0}
    assert c["loose"].parent is None


def test_span_is_recorded_when_the_body_raises():
    rec = trace.Recorder()
    with pytest.raises(ValueError):
        with rec.span("fails"):
            raise ValueError("x")
    (r,) = rec.records()
    assert r.name == "fails" and r.t1 >= r.t0 and rec.current() is None


def test_ring_keeps_the_newest_records():
    rec = trace.Recorder(capacity=8)
    for i in range(20):
        with rec.span(f"s{i}"):
            pass
    assert [r.name for r in rec.records()] == [f"s{i}" for i in range(12, 20)]


def test_records_between_two_times():
    rec = trace.Recorder()
    with rec.span("before"):
        pass
    t0 = time.perf_counter()
    with rec.span("inside"):
        rec.count("n", 1)
    t1 = time.perf_counter()
    with rec.span("after"):
        pass
    assert [r.name for r in rec.records(t0, t1)] == ["n", "inside"]
    assert [r.name for r in rec.records(t0)] == ["n", "inside", "after"]


def test_spanned_keeps_name_and_signature():
    def f(a, b=2):
        """Doc."""
        return trace.current().name, a + b

    g = trace.spanned("unit.f")(f)
    assert g.__name__ == "f" and g.__doc__ == "Doc."
    assert inspect.signature(g) == inspect.signature(f)
    assert g(1) == ("unit.f", 3)
    assert inspect.signature(serve_batch).parameters["requests"]
    assert "index" in inspect.signature(
        DetectionEngine._tiled_prologue).parameters


def test_attributes_reach_the_annotation():
    """Attributes of any type (numpy ints, lists) are accepted."""
    with trace.span("unit.attrs", rids=[1, 2], n=np.int64(3)) as sp:
        sp.set(bytes=np.int64(5), name="x")
    assert sp.attrs == {"rids": [1, 2], "n": 3, "bytes": 5, "name": "x"}


def test_table_totals_spans_and_counters():
    rec = trace.Recorder()
    for _ in range(3):
        with rec.span("step"):
            rec.count("bytes", 10)
    text = trace.table(rec.records())
    assert "step" in text and " 3 " in text
    assert "bytes" in text and "30" in text


# ---------------------------------------------------------------------------
# the chunk stage thread
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [0, 2])
def test_stage_spans_link_to_the_scan_and_feed_the_stall_fields(depth):
    t0 = time.perf_counter()
    with trace.span("engine.scan") as scan:
        pf = ChunkPrefetcher(list(range(4)), lambda d: time.sleep(0.002)
                             or d, depth=depth)
        try:
            assert list(pf) == [0, 1, 2, 3]
        finally:
            pf.close()
    recs = [r for r in trace.records(t0) if r.t0 <= scan.t1]
    stage = [r for r in recs if r.name == "engine.scan.stage"]
    wait = [r for r in recs if r.name == "engine.scan.wait"]
    assert len(stage) == 4
    assert all(r.parent == scan.id for r in stage + wait)
    threads = {r.thread for r in stage}
    if depth == 0:
        assert threads == {scan.thread} and not wait
        assert pf.stage_wait_s == pf.staging_s
    else:
        assert scan.thread not in threads and len(threads) == 1
        assert {r.thread for r in wait} == {scan.thread}
        assert len(wait) == 5                  # four groups and the end
        assert pf.stage_wait_s == pytest.approx(
            sum(r.seconds for r in wait), abs=1e-12)
    assert pf.staging_s == pytest.approx(sum(r.seconds for r in stage),
                                         abs=1e-12)


# ---------------------------------------------------------------------------
# one served pass
# ---------------------------------------------------------------------------

TREE = {
    "service.batch": ["service.stage_rows", "index.commit",
                      "engine.mask_delta", "engine.detect", "index.rollback",
                      "engine.mask_delta", "service.respond"],
    "engine.detect": ["engine.prologue", "engine.scan", "engine.finalize"],
    "engine.prologue": ["engine.chunk_gather", "engine.bucket_deltas",
                        "engine.tile_masks"],
    "engine.finalize": ["engine.decide", "engine.rescore", "engine.decide"],
}


def test_one_pass_yields_the_span_tree():
    svc = _service()
    _serve(svc, [90, 91])                       # warm: compiles, mask cache
    t0 = time.perf_counter()
    _serve(svc, [1, 2, 3])
    _serve(svc, [4])
    recs = trace.records(t0)
    kids = _children(recs)
    batches = [r for r in recs if r.name == "service.batch"]
    assert [b.attrs["rids"] for b in batches] == [[1, 2, 3], [4]]
    assert [b.attrs["requests"] for b in batches] == [3, 1]
    assert [b.attrs["rows"] for b in batches] == [6, 2]
    by_id = {r.id: r for r in _spans(recs)}
    for b in batches:
        assert b.parent is None
        todo = [b]
        while todo:
            sp = todo.pop()
            names = [c.name for c in sorted(kids[sp.id], key=lambda r: r.t0)]
            if sp.name in TREE:
                assert names == TREE[sp.name], sp.name
            todo.extend(kids[sp.id])
        det = next(c for c in kids[b.id] if c.name == "engine.detect")
        assert det.attrs["mode"] == "bucketed"
        pro = next(c for c in kids[det.id] if c.name == "engine.prologue")
        assert pro.attrs["mask_source"] == "cache"
        assert pro.attrs["chunks"] == svc.engine.last_stats["chunks"]
        assert pro.attrs["width"] == svc.engine.last_stats["chunk_width"]
        scan = next(c for c in kids[det.id] if c.name == "engine.scan")
        inside = Counter(c.name for c in kids[scan.id])
        groups = scan.attrs["groups"]
        assert inside == {"engine.scan.wait": groups + 1,
                          "engine.scan.dispatch": groups,
                          "engine.scan.collect": 1}
        stages = [r for r in _spans(recs) if r.parent == scan.id
                  and r.name == "engine.scan.stage"]
        assert len(stages) == groups
        assert all(r.thread != scan.thread for r in stages)
        fin = next(c for c in kids[det.id] if c.name == "engine.finalize")
        resc = next(c for c in kids[fin.id] if c.name == "engine.rescore")
        assert resc.attrs["pairs"] >= 0
        for r in _spans(recs):
            if r.parent in by_id and by_id[r.parent].thread == r.thread:
                par = by_id[r.parent]
                assert par.t0 <= r.t0 <= r.t1 <= par.t1


def test_h2d_bytes_equal_the_staged_slabs():
    """What a pass ships host to device is the base store's upload, once,
    inside ``engine.chunk_gather``: every chunk at the tile-padded rows,
    and no group slab staged from the host."""
    svc = _service()
    _serve(svc, [90])
    eng = svc.engine
    staged, shipped = [], []
    real_stage, real_upload = eng._stage_v, devchunks.upload

    def counting(v_np, dtype):
        staged.append(np.asarray(v_np, np.dtype(dtype)).nbytes)
        return real_stage(v_np, dtype)

    def uploading(store, s_pad, sharding):
        out = real_upload(store, s_pad, sharding)
        assert store.capacity >= s_pad
        assert out[1] == store.n_chunks * store.chunk_entries * s_pad
        shipped.append(out[1])
        return out

    eng._stage_v = counting
    devchunks.upload = uploading
    try:
        t0 = time.perf_counter()
        _serve(svc, [5, 6])
    finally:
        devchunks.upload = real_upload
    recs = trace.records(t0)
    (batch,) = [r for r in recs if r.name == "service.batch"]
    (gather,) = [r for r in recs if r.name == "engine.chunk_gather"]
    h2d = [r for r in recs if r.name == "engine.h2d_bytes"]
    assert not staged and len(shipped) == 1
    assert [r.value for r in h2d] == shipped
    assert all(gather.t0 <= r.t0 <= gather.t1 for r in h2d)
    assert all(batch.t0 <= r.t0 <= batch.t1 for r in h2d)
    stage = [r for r in recs if r.name == "engine.scan.stage"]
    assert stage and not any("bytes" in r.attrs for r in stage)


def test_device_gathered_chunks_and_the_gather_attribute():
    """``engine.device_gathered_chunks`` counts the chunks the scan took
    from the resident base, one event per group on the stage thread;
    ``engine.chunk_gather`` names where the slabs were gathered."""
    svc = _service()
    _serve(svc, [90])
    t0 = time.perf_counter()
    _serve(svc, [7, 8])
    recs = trace.records(t0)
    st = svc.engine.last_stats
    (gather,) = [r for r in recs if r.name == "engine.chunk_gather"]
    assert gather.attrs["gather"] == st["gather"] == "device"
    got = [r for r in recs if r.name == "engine.device_gathered_chunks"]
    stages = {r.id for r in recs if r.name == "engine.scan.stage"}
    assert got and all(r.parent in stages for r in got)
    assert sum(r.value for r in got) == st["chunks"]

    # the host gather where the resident base would not fit the device
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(devchunks, "fits", lambda devices, nbytes: False)
        t0 = time.perf_counter()
        _serve(svc, [9])
    recs = trace.records(t0)
    (gather,) = [r for r in recs if r.name == "engine.chunk_gather"]
    assert gather.attrs["gather"] == svc.engine.last_stats["gather"] == "host"
    assert not [r for r in recs if r.name == "engine.device_gathered_chunks"]


def test_index_build_span_counts_entries():
    ds, p = _world(3)
    t0 = time.perf_counter()
    eng = DetectionEngine(CFG, mode="bucketed", tile=32, devices=1)
    eng.detect(ds, p)
    recs = trace.records(t0)
    (build,) = [r for r in recs if r.name == "index.build"]
    (pro,) = [r for r in recs if r.name == "engine.prologue"]
    assert build.parent == pro.id and build.attrs["entries"] > 0
    assert pro.attrs["mask_source"] == "fresh"


# ---------------------------------------------------------------------------
# the shared clock with a profiler trace
# ---------------------------------------------------------------------------

def test_spans_share_the_profiler_clock(tmp_path):
    import jax
    from jax.profiler import ProfileData

    svc = _service()
    _serve(svc, [90])
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.perf_counter()
        _serve(svc, [7, 8])
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    spans = [r for r in trace.records(t0, t1) if r.value is None
             and r.t1 <= t1]
    assert len(spans) > 20
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events[ev.name].append(ev.start_ns * 1e-9)
    offsets = []
    for name in {r.name for r in spans}:
        mine = sorted(r.t0 for r in spans if r.name == name)
        theirs = sorted(events[name])
        assert len(theirs) == len(mine), name
        offsets += [e - t for e, t in zip(theirs, mine)]
    mid = float(np.median(offsets))
    assert max(abs(o - mid) for o in offsets) < 1e-3
