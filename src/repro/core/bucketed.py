"""The INDEX algorithm (§III) in two forms.

``index_detect_exact``  — entry-sequential reference with the paper's exact
    computation accounting (Ex. 3.6: 26 pairs, 51 shared values, 154
    computations on the motivating example). NumPy; the oracle for the
    production path and the source of the paper-metric counters.

``bucketed_index_detect`` — compatibility wrapper over the production path,
    which now lives in the pair-tiled, sharded ``DetectionEngine``
    (core/engine.py, DESIGN.md §3). The bucket machinery stays here:
    entries sorted by contribution score are partitioned into K contiguous
    buckets with representative probability p̂_k (``pad_buckets``), the
    same-value accumulation becomes co-occurrence matmuls ``V_k V_kᵀ``
    combined with per-pair score tables ``f(A_i, A_j, p̂_k)``, and the
    different-value penalty is recovered from ``(l − n)·ln(1−s)`` exactly as
    the paper's step 3. Pairs within ``rescore_margin`` of the decision
    boundary are exactly rescored, so binary decisions match the exact
    algorithm. ``_bucketed_accumulate`` remains as the single-device oracle
    the distributed/tiled paths are tested against.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import BucketedIndex, InvertedIndex, build_index
from repro.core.scoring import (
    decide_copying,
    posterior_independence,
    score_same,
    score_same_np,
)
from repro.core.types import ClaimsDataset, CopyConfig, DetectionResult
from repro.utils.counters import ComputeCounter


# ---------------------------------------------------------------------------
# Exact INDEX (reference + paper-metric accounting)
# ---------------------------------------------------------------------------

def index_detect_exact(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    index: InvertedIndex | None = None,
) -> DetectionResult:
    """Algorithm INDEX, steps 1–3 (§III), entry-sequential."""
    t0 = time.perf_counter()
    idx = index if index is not None else build_index(ds, p_claim, cfg)
    S = ds.n_sources
    acc = ds.accuracy.astype(np.float64)

    c_same = np.zeros((S, S), dtype=np.float64)
    n_counts = np.zeros((S, S), dtype=np.int32)
    considered = np.zeros((S, S), dtype=bool)
    values_examined = 0

    # Scan non-Ē entries first, then Ē entries: for a fresh index this IS
    # the physical 0..E−1 order (Ē is the score suffix); for a committed
    # index (base + delta chunks, Ē as a mask — DESIGN.md §7) the split
    # restores the invariant step 2 relies on — every Ē entry sees the
    # FINAL considered set, exactly as in the score-ordered scan.
    nonebar = idx.nonebar_mask
    live = idx.live_mask
    scan_order = np.concatenate([np.nonzero(nonebar)[0],
                                 np.nonzero(live & ~nonebar)[0]])
    n_nonebar = int(nonebar.sum())
    for rank, e in enumerate(scan_order):
        srcs = idx.providers(e)
        if len(srcs) < 2:
            continue
        in_ebar = rank >= n_nonebar
        a = acc[srcs]
        # f[i, j] = C→ contribution for (copier=srcs[i], source=srcs[j])
        f = score_same_np(float(idx.entry_p[e]), a[:, None], a[None, :], cfg.s, cfg.n)
        sub = np.ix_(srcs, srcs)
        if not in_ebar:
            # Step 1: every provider pair
            pairmask = np.ones((len(srcs), len(srcs)), dtype=bool)
            np.fill_diagonal(pairmask, False)
            considered[sub] |= pairmask
        else:
            # Step 2: only pairs encountered before
            pairmask = considered[sub].copy()
            np.fill_diagonal(pairmask, False)
        c_same[sub] += np.where(pairmask, f, 0.0)
        n_counts[sub] += pairmask.astype(np.int32)
        values_examined += int(np.triu(pairmask, 1).sum())

    # Step 3: different-value adjustment for considered pairs
    c_fwd = np.where(
        considered, c_same + (idx.l_counts - n_counts) * cfg.ln_1ms, 0.0
    ).astype(np.float32)
    np.fill_diagonal(c_fwd, 0.0)

    pr_ind = np.array(posterior_independence(jnp.asarray(c_fwd), jnp.asarray(c_fwd.T), cfg))
    copying = np.array(decide_copying(jnp.asarray(c_fwd), jnp.asarray(c_fwd.T), cfg))
    # pairs never considered ⇒ no-copying with Pr⊥ > .5 (paper's Ē argument)
    pr_ind = np.where(considered, pr_ind, 1.0)
    copying = copying & considered
    np.fill_diagonal(pr_ind, 1.0)
    np.fill_diagonal(copying, False)

    n_pairs = int(np.triu(considered, 1).sum())
    counter = ComputeCounter(
        pairs_considered=n_pairs,
        shared_values_examined=values_examined,
        score_computations=2 * values_examined + 2 * n_pairs,
        index_entries=idx.n_entries,
    )
    return DetectionResult(c_fwd=c_fwd, pr_independent=pr_ind, copying=copying,
                           counter=counter, wall_time_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Bucketed INDEX (production)
# ---------------------------------------------------------------------------

@dataclass
class PaddedBuckets:
    """Score-ordered index padded to (K, S, w) for fixed-shape bucket scans."""

    v_ksw: jnp.ndarray        # (K, S, w) — incidence per bucket, zero-padded
    p_hat: jnp.ndarray        # (K,)
    m_suffix: jnp.ndarray     # (K+1,)
    ebar_bucket: int
    width: int

    @property
    def n_buckets(self) -> int:
        """K — number of buckets (leading axis of v_ksw)."""
        return self.v_ksw.shape[0]


def pad_buckets(b: BucketedIndex, dtype=None) -> PaddedBuckets:
    """dtype defaults to bf16 on TPU (halves HBM traffic) and f32 on CPU
    (bf16 matmuls are emulated ~10× slower there).

    NOTE: this materializes the full (K, S, w) bucket tensor — it remains
    only as the single-device oracle / legacy-baseline form. Production
    paths stream chunks from the ``CorpusStore`` instead (the engine via
    ``engine_order``, BOUND via ``_bound_stream``)."""
    if dtype is None:
        dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    idx = b.index
    K = b.n_buckets
    S = idx.n_sources
    w = int(max(np.diff(b.starts))) if K else 1
    v = np.zeros((K, S, w), dtype=np.float32)
    for k in range(K):
        s0, s1 = int(b.starts[k]), int(b.starts[k + 1])
        v[k, :, : s1 - s0] = idx.store.slice_entries(s0, s1, dtype=np.float32)
    return PaddedBuckets(
        v_ksw=jnp.asarray(v, dtype=dtype),
        p_hat=jnp.asarray(b.p_hat, dtype=jnp.float32),
        m_suffix=jnp.asarray(b.m_suffix, dtype=jnp.float32),
        ebar_bucket=b.ebar_bucket,
        width=w,
    )


@partial(jax.jit, static_argnames=("s", "n", "ebar_bucket"))
def _bucketed_accumulate(v_ksw, p_hat, acc, s, n, ebar_bucket):
    """Scan over buckets: C_same→, shared counts n, counts outside Ē.

    C_same→[i,j] = Σ_k f→(A_i, A_j, p̂_k) · (V_k V_kᵀ)[i,j]
    """
    S = v_ksw.shape[1]
    f_a1 = acc[:, None]   # copier accuracy (rows)
    f_a2 = acc[None, :]   # source accuracy (cols)

    def body(carry, xs):
        c_same, n_cnt, n_out = carry
        v_k, p_k, k = xs
        count = jnp.dot(v_k, v_k.T, preferred_element_type=jnp.float32)
        f = score_same(p_k, f_a1, f_a2, s, n)
        c_same = c_same + f * count
        n_cnt = n_cnt + count
        n_out = n_out + jnp.where(k < ebar_bucket, count, 0.0)
        return (c_same, n_cnt, n_out), None

    init = (jnp.zeros((S, S), jnp.float32),) * 3
    ks = jnp.arange(v_ksw.shape[0])
    (c_same, n_cnt, n_out), _ = jax.lax.scan(body, init, (v_ksw, p_hat, ks))
    return c_same, n_cnt, n_out


def bucketed_index_detect(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    n_buckets: int = 64,
    rescore_margin: float = 1.0,
    index: InvertedIndex | None = None,
    tile: int = 256,
    devices: int | None = None,
) -> DetectionResult:
    """Production INDEX — routes through the pair-tiled DetectionEngine."""
    from repro.core.engine import DetectionEngine

    eng = DetectionEngine(cfg, mode="bucketed", n_buckets=n_buckets,
                          rescore_margin=rescore_margin, tile=tile,
                          devices=devices)
    return eng.detect(ds, p_claim, index=index)
