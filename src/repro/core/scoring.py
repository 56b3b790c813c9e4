"""Exact Bayesian pair scoring — Eqs. (2)–(8) of the paper.

This module is the *oracle*: the exhaustive PAIRWISE algorithm (§II-B) in a
vectorized form. Every scalable algorithm in this package (INDEX, BOUND,
HYBRID, INCREMENTAL, the Pallas kernel) is validated against it.

Conventions:
  C→[i, j] accumulates evidence that source i copies from source j
  ("S1 → S2" in the paper with S1 = i, S2 = j); the same-value contribution
  (Eq. 6) uses Pr(Φ_D(S2)) with S2 = j, the *copied* source. By symmetry of
  the observation, C←[i, j] = C→[j, i]: the backward matrix is the
  transpose, so we only ever materialize C→.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import ClaimsDataset, CopyConfig, DetectionResult
from repro.utils import trace
from repro.utils.counters import ComputeCounter


# --------------------------------------------------------------------------
# Per-item contribution scores
# --------------------------------------------------------------------------

def pr_phi_source(p, a2):
    """Eq. (4): probability of observing S2's value — P·A2 + (1−P)(1−A2)."""
    return p * a2 + (1.0 - p) * (1.0 - a2)


def pr_independent(p, a1, a2, n):
    """Eq. (3): P·A1·A2 + (1−P)(1−A1)(1−A2)/n."""
    return p * a1 * a2 + (1.0 - p) * (1.0 - a1) * (1.0 - a2) / n


def score_same(p, a_copier, a_source, s, n):
    """Eq. (6): C→(D) for a shared value with truth probability p.

    a_copier = A(S1), a_source = A(S2).  Positive, larger for lower p.
    """
    ratio = pr_phi_source(p, a_source) / pr_independent(p, a_copier, a_source, n)
    return jnp.log(1.0 - s + s * ratio)


def score_same_np(p, a_copier, a_source, s, n):
    """NumPy twin of ``score_same`` (host-side index/bound bookkeeping)."""
    ratio = (p * a_source + (1 - p) * (1 - a_source)) / (
        p * a_copier * a_source + (1 - p) * (1 - a_copier) * (1 - a_source) / n
    )
    return np.log(1.0 - s + s * ratio)


# Inflation + slack on top of the sampled maximum of the δ sweep below: the
# accuracy sweep is a grid, not an analytic bound — |f(p) − f(p̂)| can peak at
# interior accuracies (≲2e-3/entry beyond the corner max at default s, n),
# and f's monotonicity in p is conditional (tests/test_properties.py).
DELTA_INFLATION = 1.5
DELTA_SLACK = 2e-3


def bucket_score_deltas(p_hat, p_lo, p_hi, acc: np.ndarray, cfg: CopyConfig,
                        inflation: float = DELTA_INFLATION,
                        slack: float = DELTA_SLACK) -> np.ndarray:
    """Per-bucket bound δ_k ≳ |f(A_i, A_j, p) − f(A_i, A_j, p̂_k)|.

    For any entry probability p in bucket k's [p_lo, p_hi] range: the
    extremes are swept against a grid of dataset accuracy quantiles, then
    inflated to cover interior maxima the grid misses. The sweep covers both
    role orders, so one δ_k bounds f→ and f← alike. Shared by the engine's
    tiled error channel (DESIGN.md §3.4) and BOUND's error-aware freezes
    (§2.2) — with it, accumulated Σ δ_k·count bounds the p̂ approximation of
    any pair score, which is what makes approximate decisions provably equal
    the exact INDEX for ANY bucketing or chunk layout (DESIGN.md §7).
    """
    a_grid = np.unique(np.quantile(acc.astype(np.float64),
                                   [0.0, 0.25, 0.5, 0.75, 1.0]))
    p_hat = np.asarray(p_hat, np.float64)
    delta = np.zeros(len(p_hat), np.float64)
    for a1 in a_grid:
        for a2 in a_grid:
            f_hat = score_same_np(p_hat, a1, a2, cfg.s, cfg.n)
            for pe in (np.asarray(p_lo, np.float64),
                       np.asarray(p_hi, np.float64)):
                f_edge = score_same_np(pe, a1, a2, cfg.s, cfg.n)
                delta = np.maximum(delta, np.abs(f_edge - f_hat))
    return (inflation * delta + slack).astype(np.float32)


def posterior_independence(c_fwd, c_bwd, cfg: CopyConfig):
    """Eq. (2) computed stably:  Pr(⊥|Φ) = σ(−(ln(α/β) + logaddexp(C→, C←)))."""
    log_ratio = np.log(cfg.alpha / cfg.beta)
    z = log_ratio + jnp.logaddexp(c_fwd, c_bwd)
    return jax.nn.sigmoid(-z)


def decide_copying(c_fwd, c_bwd, cfg: CopyConfig):
    """copying ⟺ Pr(⊥|Φ) ≤ .5 ⟺ ln(α/β) + logaddexp(C→, C←) ≥ 0."""
    return (np.log(cfg.alpha / cfg.beta) + jnp.logaddexp(c_fwd, c_bwd)) >= 0.0


def posterior_independence_np(c_fwd, c_bwd, cfg: CopyConfig):
    """NumPy twin of ``posterior_independence``; clips z to ±60 before the
    sigmoid so float32 never overflows. (S, S) in → (S, S) float32 out."""
    z = np.log(cfg.alpha / cfg.beta) + np.logaddexp(c_fwd, c_bwd)
    out = np.empty_like(z, dtype=np.float64)
    np.clip(z, -60.0, 60.0, out=out)
    return (1.0 / (1.0 + np.exp(out))).astype(np.float32)


def decide_copying_np(c_fwd, c_bwd, cfg: CopyConfig):
    """NumPy twin of ``decide_copying``: bool matrix, True ⟺ Pr(⊥|Φ) ≤ .5."""
    return (np.log(cfg.alpha / cfg.beta) + np.logaddexp(c_fwd, c_bwd)) >= 0.0


# --------------------------------------------------------------------------
# PAIRWISE — exhaustive detection (the paper's baseline, §II-B)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("s", "n"))
def _pairwise_block(vals_i, p_i, acc_i, vals_j, p_j, acc_j, s, n):
    """C→ for a (bi, bj) block of source pairs.

    vals_i (bi, D) int32, p_i (bi, D) — truth prob of the value i provides.
    Returns (bi, bj) C→ block:  i copies from j.
    """
    prov_i = (vals_i >= 0)[:, None, :]                    # (bi, 1, D)
    prov_j = (vals_j >= 0)[None, :, :]                    # (1, bj, D)
    shared = prov_i & prov_j
    same = shared & (vals_i[:, None, :] == vals_j[None, :, :])
    p = p_i[:, None, :]                                   # value prob (same value ⇒ same p)
    a1 = acc_i[:, None, None]
    a2 = acc_j[None, :, None]
    sc = score_same(p, a1, a2, s, n)                      # (bi, bj, D)
    ln1ms = jnp.log(1.0 - s)
    contrib = jnp.where(same, sc, jnp.where(shared, ln1ms, 0.0))
    return contrib.sum(axis=-1)


def pairwise_detect(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    block: int = 128,
) -> DetectionResult:
    """Exhaustive PAIRWISE copy detection. O(|S|²·|D|) work.

    p_claim[s, d]: probability that the value source s provides on item d is
    true (P(D.v) for v = values[s, d]); ignored where values[s, d] < 0.
    """
    t0 = time.perf_counter()
    S, D = ds.values.shape
    vals = jnp.asarray(ds.values)
    p = jnp.asarray(p_claim, dtype=jnp.float32)
    acc = jnp.asarray(ds.accuracy, dtype=jnp.float32)

    c_fwd = np.zeros((S, S), dtype=np.float32)
    for i0 in range(0, S, block):
        i1 = min(i0 + block, S)
        for j0 in range(0, S, block):
            j1 = min(j0 + block, S)
            blk = _pairwise_block(
                vals[i0:i1], p[i0:i1], acc[i0:i1],
                vals[j0:j1], p[j0:j1], acc[j0:j1],
                cfg.s, cfg.n,
            )
            c_fwd[i0:i1, j0:j1] = np.asarray(blk)
    np.fill_diagonal(c_fwd, 0.0)

    pr_ind = np.array(posterior_independence(jnp.asarray(c_fwd), jnp.asarray(c_fwd.T), cfg))
    copying = np.array(decide_copying(jnp.asarray(c_fwd), jnp.asarray(c_fwd.T), cfg))
    np.fill_diagonal(pr_ind, 1.0)
    np.fill_diagonal(copying, False)

    # Paper's computation accounting (Ex. 3.6): PAIRWISE examines every shared
    # item of every pair, 2 computations each (C→ and C←), over unordered pairs.
    prov = ds.provided_mask.astype(np.int64)
    l_counts = prov @ prov.T
    iu = np.triu_indices(S, k=1)
    shared_items = int(l_counts[iu].sum())
    counter = ComputeCounter(
        pairs_considered=S * (S - 1) // 2,
        shared_values_examined=shared_items,
        score_computations=2 * shared_items,
    )
    return DetectionResult(
        c_fwd=c_fwd,
        pr_independent=pr_ind,
        copying=copying,
        counter=counter,
        wall_time_s=time.perf_counter() - t0,
    )


#: Most pairs scored per device call. Each call gathers (pairs, items)
#: claim rows, so the block bounds device memory (a Book-full batch can
#: rescore ~650 k pairs × 20 k items, 52 GB gathered at once); shorter
#: lists pad to a power of two, so few shapes are ever compiled.
PAIR_BLOCK = 4096


def pair_scores_subset(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    pairs_i: np.ndarray,
    pairs_j: np.ndarray,
) -> np.ndarray:
    """Exact C→ for an explicit list of pairs (used for near-threshold
    rescoring by the bucketed algorithms). Returns (n_pairs,) C→[i, j]."""
    n_pairs = len(pairs_i)
    block = min(PAIR_BLOCK, 1 << max(n_pairs - 1, 0).bit_length())
    pad = (-n_pairs) % block
    pi = np.pad(np.asarray(pairs_i, np.int32), (0, pad))
    pj = np.pad(np.asarray(pairs_j, np.int32), (0, pad))
    vals = jnp.asarray(ds.values)
    p = jnp.asarray(p_claim, dtype=jnp.float32)
    acc = jnp.asarray(ds.accuracy, dtype=jnp.float32)
    out = [_pair_list_scores(vals, p, acc, jnp.asarray(pi[k:k + block]),
                             jnp.asarray(pj[k:k + block]), cfg.s, cfg.n)
           for k in range(0, len(pi), block)]
    return np.concatenate([np.asarray(o) for o in out])[:n_pairs]


@trace.spanned("engine.rescore")
def rescore_pairs_exact(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    pi: np.ndarray,
    pj: np.ndarray,
    c_fwd: np.ndarray,
) -> int:
    """Gathered dense exact rescore of an explicit flip-candidate pair list.

    This is the batched op DESIGN.md §2.3 collapses the paper's §V
    compensation passes into, shared by every caller that must replace
    approximate pair scores with exact ones: INCREMENTAL's flip candidates,
    the engine's error-bounded near-threshold pairs (DESIGN.md §3 step 4),
    and SAMPLE-THEN-VERIFY's candidate set (DESIGN.md §4).

    Args:
      ds, p_claim, cfg: the *full* dataset, per-claim truth probabilities
        (S, D), and model config the exact scores are computed against.
      pi, pj: (P,) int arrays of source indices — the unordered pairs to
        rescore (each listed once; both orientations are written).
      c_fwd: (S, S) float32 C→ matrix, mutated in place at [pi, pj] and
        [pj, pi] with exact Eq. 2–8 scores over all shared items.

    Returns the number of pairs rescored (0 for an empty list).
    """
    trace.annotate(pairs=len(pi))
    if len(pi) == 0:
        return 0
    both = pair_scores_subset(ds, p_claim, cfg, np.concatenate([pi, pj]),
                              np.concatenate([pj, pi]))
    c_fwd[pi, pj] = both[:len(pi)]
    c_fwd[pj, pi] = both[len(pi):]
    return len(pi)


@partial(jax.jit, static_argnames=("s", "n"))
def _pair_list_scores(vals, p, acc, pi, pj, s, n):
    vi, vj = vals[pi], vals[pj]                           # (P, D)
    shared = (vi >= 0) & (vj >= 0)
    same = shared & (vi == vj)
    sc = score_same(p[pi], acc[pi][:, None], acc[pj][:, None], s, n)
    contrib = jnp.where(same, sc, jnp.where(shared, jnp.log(1.0 - s), 0.0))
    return contrib.sum(axis=-1)
