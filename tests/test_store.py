"""CorpusStore (DESIGN.md §6): chunked incidence is bit-exact vs dense,
row slack works, build peak allocation respects the chunk-bytes cap, and the
synthetic-claims spec validation fails fast instead of spinning."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CopyConfig, DetectionEngine, build_index
from repro.core.bucketed import index_detect_exact
from repro.core.index import engine_order
from repro.core.store import align_chunk
from repro.core.types import ClaimsDataset
from repro.data.claims import (
    SyntheticSpec,
    oracle_claim_probs,
    synthetic_claims,
)

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0)


def _random_world(seed: int, n_src: int = 24, n_items: int = 80):
    rng = np.random.default_rng(seed)
    values = np.where(rng.random((n_src, n_items)) < 0.6,
                      rng.integers(0, 4, (n_src, n_items)), -1).astype(np.int32)
    ds = ClaimsDataset(values=values,
                       accuracy=rng.uniform(0.1, 0.95, n_src).astype(np.float32))
    p = np.where(values == 0, 0.9, 0.05).astype(np.float32)
    return ds, p


# ---------------------------------------------------------------------------
# chunked == dense, bit for bit
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), chunk=st.integers(1, 96),
       n_src=st.integers(4, 24), n_items=st.integers(10, 90))
def test_chunked_build_bit_exact_vs_dense(seed, chunk, n_src, n_items):
    """ISSUE 4: chunked-store gather is bit-exact vs the dense incidence for
    random claim sets and chunk widths."""
    ds, p = _random_world(seed, n_src, n_items)
    idx_c = build_index(ds, p, CFG, chunk_entries=chunk)
    idx_d = build_index(ds, p, CFG, chunk_entries=1 << 22)
    assert idx_d.store.n_chunks <= 1
    assert idx_c.store.chunk_entries == align_chunk(chunk)
    np.testing.assert_array_equal(idx_c.store.to_dense(), idx_d.store.to_dense())
    np.testing.assert_array_equal(idx_c.entry_item, idx_d.entry_item)
    np.testing.assert_array_equal(idx_c.entry_p, idx_d.entry_p)
    np.testing.assert_array_equal(idx_c.entry_score, idx_d.entry_score)
    assert idx_c.ebar_start == idx_d.ebar_start
    # every chunk respects the width bound — the peak-allocation guarantee
    for ch in idx_c.store.iter_chunks():
        assert ch.width <= idx_c.store.chunk_entries


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), chunk=st.integers(1, 64),
       lo=st.integers(0, 40), width=st.integers(0, 40))
def test_slice_and_gather_bit_exact(seed, chunk, lo, width):
    """slice_entries / gather_entries / cooccurrence agree with the dense
    forms for any chunking, range, and dtype conversion."""
    ds, p = _random_world(seed)
    idx = build_index(ds, p, CFG, chunk_entries=chunk)
    E = idx.n_entries
    dense = idx.store.to_dense()
    e0 = min(lo, E)
    e1 = min(lo + width, E)
    for dtype in (np.int8, np.float32):
        np.testing.assert_array_equal(
            idx.store.slice_entries(e0, e1, dtype=dtype),
            dense[:, e0:e1].astype(dtype))
    rng = np.random.default_rng(seed)
    order = rng.permutation(E)
    g = idx.store.gather_entries(order, chunk_entries=max(chunk // 2, 1))
    np.testing.assert_array_equal(g.to_dense(), dense[:, order])
    np.testing.assert_array_equal(g.entry_p, idx.entry_p[order])
    # -1 markers become inert zero columns
    order2 = np.concatenate([order[: E // 2], [-1, -1]])
    g2 = idx.store.gather_entries(order2)
    np.testing.assert_array_equal(g2.to_dense()[:, -2:], 0)
    assert (g2.entry_item[-2:] == -1).all()
    # chunk-streamed co-occurrence == dense matmul (exact integer f32 sums)
    d32 = dense.astype(np.float32)
    np.testing.assert_array_equal(idx.store.cooccurrence(), d32 @ d32.T)
    np.testing.assert_array_equal(
        idx.store.cooccurrence(stop=idx.ebar_start),
        d32[:, : idx.ebar_start] @ d32[:, : idx.ebar_start].T)


def test_engine_chunks_layout():
    """engine_order + gather: uniform width, chunk-aligned Ē boundary, live
    p̂ stats."""
    ds, p = _random_world(5, n_src=32, n_items=120)
    idx = build_index(ds, p, CFG, chunk_entries=16)
    ech = engine_order(idx, n_buckets=8)
    ech.gather(idx, row_capacity=40)
    b = ech.width
    assert b % 8 == 0
    assert ech.store.capacity == 40
    for ch in ech.store.iter_chunks():
        assert ch.width == b
    # every live entry appears exactly once; padding columns are inert
    live = ech.store.entry_item >= 0
    assert int(live.sum()) == idx.n_entries == ech.n_live
    assert ech.store.to_dense()[:, ~live].sum() == 0
    # Ē boundary is chunk-aligned: non-Ē live entries fill chunks < ebar_chunk
    starts = np.arange(ech.store.n_entries) // b
    nonebar_chunks = set(starts[live][: idx.ebar_start]
                         if idx.ebar_start else [])
    assert all(c < ech.ebar_chunk for c in nonebar_chunks)
    assert (ech.nout == (np.arange(ech.n_chunks) < ech.ebar_chunk)).all()
    # per-chunk p extremes bound the live entries of that chunk
    for k in range(ech.n_chunks):
        seg = slice(k * b, (k + 1) * b)
        m = live[seg]
        if m.any():
            ps = ech.store.entry_p[seg][m]
            assert ech.p_lo[k] <= ps.min() and ech.p_hi[k] >= ps.max()


def test_copyscore_store_matches_dense_kernel():
    """The chunked full-square dispatch (ops.copyscore_store) reproduces the
    dense bucket-aligned kernel: counts bit-equal (integer-exact f32 sums),
    scores to f32 round-off (per-chunk elementwise math compiles separately
    from the dense scan's)."""
    from repro.kernels.ops import copyscore, copyscore_store

    ds, p = _random_world(9, n_src=24, n_items=100)
    idx = build_index(ds, p, CFG, chunk_entries=16)
    ech = engine_order(idx, n_buckets=6)
    ech.gather(idx)
    dense = ech.store.to_dense().astype(np.float32)
    c_d, n_d = copyscore(dense, ech.p_hat, ds.accuracy,
                         s=CFG.s, n_false=CFG.n, block_e=ech.width,
                         impl="ref")
    c_s, n_s = copyscore_store(ech.store, ech.p_hat, ds.accuracy,
                               s=CFG.s, n_false=CFG.n, impl="ref")
    np.testing.assert_array_equal(np.asarray(n_d), n_s)
    np.testing.assert_allclose(np.asarray(c_d), c_s, rtol=1e-5, atol=1e-4)


def test_serve_batch_rejects_mismatched_resident():
    """A resident built over a different corpus fails fast, not silently."""
    from repro.core.serving import DetectRequest, ResidentCorpus, serve_batch

    ds, p = _random_world(12, n_src=32, n_items=28)
    other, other_p = _random_world(13, n_src=24, n_items=28)
    rc = ResidentCorpus(other, other_p, max_query_rows=4)
    eng = DetectionEngine(CFG, mode="bucketed", tile=32)
    req = DetectRequest(rid=0, values=np.full((1, 28), -1, np.int32),
                        accuracy=np.array([0.5], np.float32),
                        p_claim=np.zeros((1, 28), np.float32))
    with pytest.raises(ValueError, match="same corpus"):
        serve_batch(ds, p, eng, [req], resident=rc)


def test_chunk_group_bytes_narrows_width_and_keeps_decisions():
    """chunk_group_bytes is a HARD per-pass ceiling: it narrows the engine
    chunk width when one n_buckets-derived chunk would exceed it, and clamps
    the group size — decisions still equal the exact INDEX."""
    ds, p = _random_world(3, n_src=48, n_items=160)
    idx = build_index(ds, p, CFG)
    wide = DetectionEngine(CFG, mode="bucketed", tile=48, n_buckets=4)
    res_w = wide.detect(ds, p, index=idx)
    budget = 48 * 8 * 2                 # two 8-entry columns of S_pad rows
    tight = DetectionEngine(CFG, mode="bucketed", tile=48, n_buckets=4,
                            chunk_group_bytes=budget, chunk_group=64)
    res_t = tight.detect(ds, p, index=idx)
    assert tight.last_stats["chunk_width"] < wide.last_stats["chunk_width"]
    assert tight.last_stats["peak_group_bytes"] <= budget
    exact = index_detect_exact(ds, p, CFG, index=idx)
    np.testing.assert_array_equal(res_w.copying, exact.copying)
    np.testing.assert_array_equal(res_t.copying, exact.copying)


# ---------------------------------------------------------------------------
# row slack: append_rows / truncate_rows
# ---------------------------------------------------------------------------

def test_append_rows_matches_rebuilt_membership():
    """Appended rows get exactly the membership bits a rebuild would give
    them for the EXISTING entry set (new shared values need a re-index)."""
    ds, p = _random_world(11, n_src=20, n_items=60)
    idx = build_index(ds, p, CFG, chunk_entries=8, row_capacity=26)
    store = idx.store
    assert store.capacity == 26
    rng = np.random.default_rng(0)
    new_rows = np.where(rng.random((4, 60)) < 0.5,
                        rng.integers(0, 4, (4, 60)), -1).astype(np.int32)
    bits = store.append_rows(new_rows)
    assert store.n_rows == 24
    dense = store.to_dense()
    expect = (new_rows[:, store.entry_item] ==
              store.entry_value[None, :]).astype(np.int8)
    np.testing.assert_array_equal(dense[20:], expect)
    assert bits == int(expect.sum())
    # truncate restores the corpus-only store exactly
    store.truncate_rows(20)
    np.testing.assert_array_equal(store.to_dense(),
                                  build_index(ds, p, CFG, chunk_entries=8)
                                  .store.to_dense())
    with pytest.raises(ValueError, match="capacity"):
        store.append_rows(np.full((7, 60), -1, np.int32))


# ---------------------------------------------------------------------------
# memory smoke: chunk-bytes cap at S=2048 (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_chunk_bytes_cap_s2048_decisions_exact():
    """Build at S=2048 under a 1 MiB chunk-bytes cap: no single incidence
    allocation exceeds the cap anywhere in the pipeline, and engine decisions
    still equal ``index_detect_exact``."""
    cap = 1 << 20
    spec = SyntheticSpec(n_sources=2048, n_items=3072, coverage="book",
                         n_cliques=50, clique_size=3, clique_items=12, seed=0)
    sc = synthetic_claims(spec)
    p = oracle_claim_probs(sc)
    idx = build_index(sc.dataset, p, CFG, chunk_bytes=cap)
    assert idx.store.n_chunks > 1, "cap must force a multi-chunk store"
    assert idx.store.max_chunk_nbytes <= cap
    # a budget that is NOT row-count-aligned still holds (width rounds DOWN)
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 3, (100, 40)).astype(np.int32)
    ds_small = ClaimsDataset(values=vals,
                             accuracy=np.full(100, 0.5, np.float32))
    p_small = np.full(vals.shape, 0.3, np.float32)
    idx_small = build_index(ds_small, p_small, CFG, chunk_bytes=1000)
    assert idx_small.store.max_chunk_nbytes <= 1000
    eng = DetectionEngine(CFG, mode="bucketed", tile=256,
                          chunk_group_bytes=cap)
    res = eng.detect(sc.dataset, p, index=idx)
    st = eng.last_stats
    # the engine's resident incidence per device pass stays under the cap too
    assert st["chunks"] > 1
    assert st["peak_group_bytes"] <= cap
    exact = index_detect_exact(sc.dataset, p, CFG, index=idx)
    np.testing.assert_array_equal(res.copying, exact.copying)


# ---------------------------------------------------------------------------
# synthetic-claims spec validation (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_synthetic_claims_rejects_oversubscribed_cliques():
    """n_cliques·clique_size > n_sources used to spin the unused-source
    rejection loop forever; now it raises up front."""
    bad = SyntheticSpec(n_sources=10, n_items=50, n_cliques=4, clique_size=3)
    with pytest.raises(ValueError, match="n_sources"):
        synthetic_claims(bad)
    # the boundary case (every source in a clique) still generates
    ok = SyntheticSpec(n_sources=12, n_items=50, n_cliques=4, clique_size=3)
    sc = synthetic_claims(ok)
    assert sc.dataset.n_sources == 12
    assert len({s for pair in sc.copies for s in pair}) <= 12
