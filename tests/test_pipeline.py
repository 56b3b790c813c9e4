"""Async double-buffered chunk staging + serve-path cache reuse (§11).

Covers the prefetcher's contract (ordering, sync fallback, typed error
propagation, no stranded threads/buffers) and the serving-layer property
the whole delta plumbing exists for: after a service ``commit()``, the next
detect reuses the incrementally-updated mask cache — ZERO full-chunk
block-OR regathers, counted by monkeypatching the one entry point
(``tilecache.chunk_block_inc``).
"""
import threading
import time

import faults
import numpy as np
import pytest

from repro.core import CopyConfig, DetectionEngine, build_index
from repro.core import devchunks, tilecache
from repro.core.pipeline import ChunkPrefetcher, PipelineStageError
from repro.core.serving import DetectRequest, DetectionService
from repro.core.types import ClaimsDataset

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0)


def _world(seed=0, n_src=40, n_items=160):
    rng = np.random.default_rng(seed)
    values = np.where(rng.random((n_src, n_items)) < 0.4,
                      rng.integers(0, 4, (n_src, n_items)),
                      -1).astype(np.int32)
    ds = ClaimsDataset(values=values,
                       accuracy=rng.uniform(0.3, 0.95,
                                            n_src).astype(np.float32))
    p = np.where(values == 0, 0.9, 0.05).astype(np.float32)
    return ds, p


def _reqs(ds, p, n=4, q=2, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        vals = np.where(rng.random((q, ds.n_items)) < 0.3,
                        rng.integers(0, 4, (q, ds.n_items)),
                        -1).astype(np.int32)
        acc = rng.uniform(0.3, 0.95, q).astype(np.float32)
        pq = np.where(vals == 0, 0.9,
                      np.where(vals >= 0, 0.05, 0.0)).astype(np.float32)
        out.append(DetectRequest(rid=i, values=vals, accuracy=acc,
                                 p_claim=pq))
    return out


# ---------------------------------------------------------------------------
# ChunkPrefetcher unit contract
# ---------------------------------------------------------------------------

def test_prefetcher_preserves_order_and_telemetry():
    """Items arrive in descriptor order at every depth; depth=0 runs inline
    (stage_wait == staging by construction), depth≥1 on a worker thread."""
    for depth in (0, 1, 3):
        staged = []

        def stage(d):
            staged.append((d, threading.current_thread()
                           is threading.main_thread()))
            return d * 10
        pf = ChunkPrefetcher(list(range(5)), stage, depth=depth)
        try:
            assert list(pf) == [0, 10, 20, 30, 40]
        finally:
            pf.close()
        assert [d for d, _ in staged] == [0, 1, 2, 3, 4]
        on_main = {m for _, m in staged}
        assert on_main == ({True} if depth == 0 else {False})
        assert pf.staging_s >= 0 and pf.stage_wait_s >= 0
        if depth == 0:
            assert pf.stage_wait_s == pf.staging_s


def test_prefetcher_raising_stage_is_a_typed_error():
    """An injected stage fault (tests/faults.py) surfaces as
    PipelineStageError with the cause preserved, the worker thread dies,
    and close() leaves nothing stranded."""
    n0 = threading.active_count()

    def stage(d):
        if d == 2:
            raise faults.InjectedFault("boom at 2")
        return d
    pf = ChunkPrefetcher(list(range(6)), stage, depth=2)
    got = []
    with pytest.raises(PipelineStageError, match="boom at 2") as ei:
        for item in pf:
            got.append(item)
    assert isinstance(ei.value.__cause__, faults.InjectedFault)
    pf.close()
    assert got == [0, 1]
    deadline = time.monotonic() + 5
    while threading.active_count() > n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() == n0


def test_prefetcher_slow_stage_keeps_order_and_counts_waits():
    """A slow stage thread never reorders items — the consumer just waits,
    and the wait shows up in stage_wait_s."""
    def stage(d):
        time.sleep(0.02)
        return d
    pf = ChunkPrefetcher(list(range(4)), stage, depth=1)
    try:
        assert list(pf) == [0, 1, 2, 3]
    finally:
        pf.close()
    assert pf.staging_s >= 0.08
    assert pf.stage_wait_s > 0


def test_engine_stage_fault_is_typed_and_engine_reusable():
    """A staging fault inside detect() raises PipelineStageError; the same
    engine object then serves the next detect normally (no stranded worker,
    no corrupted pipeline state). The fault is injected into both ways of
    staging a group: the device gather and the host slab's transfer."""
    ds, p = _world(3)
    idx = build_index(ds, p, CFG)
    eng = DetectionEngine(CFG, mode="bucketed", tile=32, prefetch_depth=2)
    ref = eng.detect(ds, p, index=idx)
    n0 = threading.active_count()

    def broken(*a, **kw):
        raise faults.InjectedFault("injected staging fault")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(DetectionEngine, "_stage_v", broken)
        mp.setattr(devchunks, "gather", broken)
        with pytest.raises(PipelineStageError, match="injected staging"):
            eng.detect(ds, p, index=idx)
    deadline = time.monotonic() + 5
    while threading.active_count() > n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() == n0
    again = eng.detect(ds, p, index=idx)
    np.testing.assert_array_equal(again.copying, ref.copying)


def test_prefetch_depths_agree_on_decisions():
    """prefetch_depth 0 / 1 / 2 produce identical decisions and stats that
    account staging consistently."""
    ds, p = _world(5)
    idx = build_index(ds, p, CFG)
    ref = None
    for depth in (0, 1, 2):
        eng = DetectionEngine(CFG, mode="bucketed", tile=32,
                              prefetch_depth=depth)
        res = eng.detect(ds, p, index=idx)
        assert eng.last_stats["prefetch_depth"] == depth
        assert eng.last_stats["staging_s"] >= 0
        if ref is None:
            ref = res
        else:
            np.testing.assert_array_equal(res.copying, ref.copying)


# ---------------------------------------------------------------------------
# serving: commit→detect does ZERO full-chunk regathers
# ---------------------------------------------------------------------------

def _count_regathers(monkeypatch):
    calls = {"n": 0}
    real = tilecache.chunk_block_inc

    def counted(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)
    monkeypatch.setattr(tilecache, "chunk_block_inc", counted)
    return calls


def test_service_commit_then_detect_zero_regathers(monkeypatch):
    """After the first (cache-building) batch, every later batch — across a
    permanent commit AND the per-batch transient commit→rollback — detects
    off the incrementally-maintained cache: zero chunk_block_inc calls."""
    ds, p = _world(9)
    svc = DetectionService(ds, p, CFG, mode="bucketed", tile=32,
                           max_batch_requests=4, result_cache=False)
    reqs = _reqs(ds, p)

    def flush(rs):
        futs = [svc.submit(r) for r in rs]
        svc.flush()
        return [f.result() for f in futs]

    flush(reqs)                               # builds the cache
    builds0 = svc.engine.last_stats["mask_full_builds"]

    calls = _count_regathers(monkeypatch)
    before = flush(reqs[:2])
    assert calls["n"] == 0, f"steady-state batch regathered {calls['n']}"
    assert svc.engine.last_stats["mask_source"] == "cache"

    rng = np.random.default_rng(10)
    vals = np.where(rng.random((3, ds.n_items)) < 0.3,
                    rng.integers(0, 4, (3, ds.n_items)), -1).astype(np.int32)
    acc = np.full(3, 0.7, np.float32)
    pq = np.where(vals == 0, 0.9,
                  np.where(vals >= 0, 0.05, 0.0)).astype(np.float32)
    calls["n"] = 0
    svc.commit(vals, acc, pq)
    after = flush(reqs[:2])
    assert calls["n"] == 0, f"commit→detect regathered {calls['n']}"
    st = svc.engine.last_stats
    assert st["mask_source"] == "cache"
    assert st["mask_full_builds"] == builds0   # never rebuilt
    assert st["mask_blocks_updated"] > 0       # but incrementally updated
    # grown corpus ⇒ responses stay well-formed for the same requests
    assert all(a.copying.shape[0] == b.copying.shape[0]
               for a, b in zip(before, after))


def test_service_retract_keeps_cache_and_matches_rebuild(monkeypatch):
    """retract() keeps the delta chain alive (touched-block recompute, no
    full rebuild) and decisions equal a from-scratch service."""
    ds, p = _world(15)
    svc = DetectionService(ds, p, CFG, mode="bucketed", tile=32,
                           max_batch_requests=4, result_cache=False)
    reqs = _reqs(ds, p)

    def flush(s, rs):
        futs = [s.submit(r) for r in rs]
        s.flush()
        return [f.result() for f in futs]

    flush(svc, reqs)
    builds0 = svc.engine.last_stats["mask_full_builds"]
    calls = _count_regathers(monkeypatch)
    svc.retract(np.array([2, 7]))
    got = flush(svc, reqs)
    assert calls["n"] == 0
    assert svc.engine.last_stats["mask_full_builds"] == builds0
    cold = DetectionService(
        ClaimsDataset(values=svc.base.values, accuracy=svc.base.accuracy),
        svc.base_p.copy(), CFG, mode="bucketed", tile=32,
        max_batch_requests=4, result_cache=False)
    ref = flush(cold, reqs)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a.copying, b.copying)
