"""INCREMENTAL detection across fusion rounds (§V).

After round 2 the per-round changes in value probability / source accuracy
are small and rarely flip decisions. We keep the paper's structure:

* classify entries into big / small score changes (|ΔM̂| > ρ, with M̂
  recomputed on the *same* two accuracies as the recorded round — §V-A);
* pass 1: apply exact per-pair deltas for big-change entries (before each
  pair's decision point) and a conservative batched bound Δρ·|Ē↘| for
  small changes; pairs still safely on their side of the threshold keep
  their decision — the paper observes ≥86–99% settle here (Table VIII);
* passes 2–3 (compensation with Ē⋈ / Ē↑ and exact small-change replay)
  are collapsed into one *exact rescoring of the flip-candidate set*
  (DESIGN.md §2.3): on TPU a gathered exact rescore of ≲2% of pairs is one
  dense batched op, strictly cheaper and decision-equivalent to the paper's
  entry-wise compensation walk. Pairs containing a source with a big
  accuracy change (|ΔA| > ρ_acc = .2) are rescored unconditionally, as in
  the paper.

The public entry point is ``DetectionEngine(cfg, mode="incremental")``
(core/engine.py), which owns the round lifecycle: the first ``detect`` call
bootstraps the state here, later calls apply per-round deltas.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.core.bound import bound_detect
from repro.core.index import (
    BucketedIndex,
    InvertedIndex,
    bucketize,
    build_index,
    entry_extreme_accuracies,
    prop31_reference_accs,
)
from repro.core.scoring import (
    decide_copying_np,
    posterior_independence_np,
    rescore_pairs_exact,
    score_same_np,
)
from repro.core.types import ClaimsDataset, CopyConfig, DetectionResult
from repro.utils import trace
from repro.utils.counters import ComputeCounter


@dataclass
class IncrementalState:
    """Bookkeeping carried across rounds (§V preparation step)."""

    index: InvertedIndex          # canonical (round-2) entry order — V is fixed
    bucketed: BucketedIndex
    entry_bucket: np.ndarray      # (E,) bucket id per entry
    first_provider: np.ndarray    # (E,) a provider per entry (for p lookup)
    p_old: np.ndarray             # (E,) last-recomputed P(E)
    score_old: np.ndarray         # (E,) M̂ with p_old
    a1_ref: np.ndarray            # (E,) Prop-3.1 accuracies of the reference round
    a2_ref: np.ndarray
    acc_old: np.ndarray           # (S,) accuracies of the reference round
    c_hat: np.ndarray             # (S,S) Ĉ→ starting scores
    copying: np.ndarray           # (S,S) current decisions
    considered: np.ndarray        # (S,S)
    dec_bucket: np.ndarray        # (S,S)
    l_counts: np.ndarray
    pass1_settled: float = 1.0
    err: np.ndarray = None        # (S,S) accumulated p̂-error bound on c_hat
                                  # (0 where a round has rescored exactly)


def make_incremental_state(
    ds: ClaimsDataset, p_claim: np.ndarray, cfg: CopyConfig,
    n_buckets: int = 64,
    chunk_entries: int | None = None,
    chunk_bytes: int | None = None,
    index: InvertedIndex | None = None,
) -> tuple[DetectionResult, IncrementalState]:
    """Run HYBRID from scratch and capture the bookkeeping for later rounds.

    ``chunk_entries`` / ``chunk_bytes`` forward to ``build_index`` — they
    pick the CorpusStore chunking the bookkeeping will iterate forever after.
    ``index`` bootstraps from a prebuilt index instead — including a
    COMMITTED one (base + delta chunk sequence, Ē as a mask): the
    bookkeeping below iterates whatever chunk layout the store has, and the
    per-entry arrays are position-indexed, so the delta layout rides along
    (DESIGN.md §7).
    """
    idx = index if index is not None else build_index(
        ds, p_claim, cfg, chunk_entries=chunk_entries, chunk_bytes=chunk_bytes)
    bucketed = bucketize(idx, n_buckets)
    result, bstate = bound_detect(
        ds, p_claim, cfg, use_timers=True, l_threshold=16,
        index=idx, bucketed=bucketed, return_state=True,
    )
    E = idx.n_entries
    entry_bucket = (np.searchsorted(bucketed.starts, np.arange(E), side="right") - 1
                    ).astype(np.int32)
    # a provider per entry, chunk by chunk (column argmax over live rows)
    first_provider = (
        np.concatenate([ch.V.argmax(axis=0) for ch in idx.store.iter_chunks()])
        if idx.store.n_chunks else np.zeros(0, np.int64)
    ).astype(np.int32)

    # Prop-3.1 reference accuracies per entry (vectorized case split)
    acc = ds.accuracy.astype(np.float64)
    amin, asec, amax = entry_extreme_accuracies(idx.store, acc)
    a1_ref, a2_ref = prop31_reference_accs(
        idx.entry_p.astype(np.float64), amin, asec, amax, cfg)

    state = IncrementalState(
        index=idx, bucketed=bucketed, entry_bucket=entry_bucket,
        first_provider=first_provider,
        p_old=idx.entry_p.copy(), score_old=idx.entry_score.copy(),
        a1_ref=a1_ref, a2_ref=a2_ref, acc_old=ds.accuracy.copy(),
        c_hat=bstate.c_hat.copy(), copying=result.copying.copy(),
        considered=bstate.considered.copy(), dec_bucket=bstate.dec_bucket.copy(),
        l_counts=idx.l_counts, err=bstate.err.copy(),
    )
    return result, state


@trace.spanned("engine.incremental")
def incremental_detect(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    state: IncrementalState,
    rho: float = 1.0,
    rho_acc: float = 0.2,
) -> DetectionResult:
    """One incremental round. Mutates ``state`` in place."""
    t0 = time.perf_counter()
    idx = state.index
    S = ds.n_sources
    E = idx.n_entries
    acc_new = ds.accuracy.astype(np.float64)

    with trace.span("incremental.pass1"):
        # new entry probabilities via any provider's claim (padding columns of a
        # committed store have no providers — clamp the lookup and zero their
        # deltas so they never join the big/small classification)
        live = idx.entry_item >= 0
        p_new = p_claim[state.first_provider,
                        np.maximum(idx.entry_item, 0)].astype(np.float32)
        p_new = np.where(live, p_new, state.p_old)
        score_new = score_same_np(
            p_new.astype(np.float64), state.a1_ref, state.a2_ref, cfg.s, cfg.n
        ).astype(np.float32)
        delta = np.where(live, score_new - state.score_old, 0.0)
        big = np.abs(delta) > rho
        small_dec = (~big) & (delta < 0)
        small_inc = (~big) & (delta > 0)

        # ---- pass 1a: exact deltas from big-change entries -------------------
        d_c = np.zeros((S, S), np.float64)
        values_examined = 0
        for e in np.nonzero(big)[0]:
            provs = idx.providers(e)
            if len(provs) < 2:
                continue
            a_new = acc_new[provs]
            a_old = state.acc_old.astype(np.float64)[provs]
            f_new = score_same_np(float(p_new[e]), a_new[:, None],
                                  a_new[None, :], cfg.s, cfg.n)
            f_old = score_same_np(float(state.p_old[e]), a_old[:, None],
                                  a_old[None, :], cfg.s, cfg.n)
            sub = np.ix_(provs, provs)
            # only update pairs whose decision point lies after this entry
            gate = state.dec_bucket[sub] >= state.entry_bucket[e]
            d_c[sub] += np.where(gate, f_new - f_old, 0.0)
            values_examined += int(np.triu(gate, 1).sum())

        # ---- pass 1b: conservative batched bound for small changes -----------
        d_rho_dec = float(-delta[small_dec].min()) if small_dec.any() else 0.0
        d_rho_inc = float(delta[small_inc].max()) if small_inc.any() else 0.0

        def _masked_counts(mask: np.ndarray) -> np.ndarray:
            # Σ_chunks V_c[:, m] V_c[:, m]ᵀ — per-chunk partial sums of 0/1
            # products are exact integers in f32, bit-equal to the dense matmul
            out = np.zeros((S, S), np.float32)
            if not mask.any():
                return out
            for ch in idx.store.iter_chunks():
                m = mask[ch.start: ch.start + ch.width]
                if m.any():
                    v = ch.V[:, m].astype(np.float32)
                    out += v @ v.T
            return out

        trace.count("incremental.big_entries", int(big.sum()))
        trace.count("incremental.masked_entries",
                    int(small_dec.sum() + small_inc.sum()))
        with trace.span("incremental.masked_counts"):
            cnt_dec = _masked_counts(small_dec)
            cnt_inc = _masked_counts(small_inc)

        c_base = state.c_hat.astype(np.float64) + d_c
        # the bootstrap's accumulated p̂-error bound (zeroed wherever a previous
        # round rescored exactly) — the keep rules must hold BEYOND it, so kept
        # decisions stay provably exact for any index layout (DESIGN.md §7)
        err = (state.err if state.err is not None
               else np.zeros((S, S), np.float32)).astype(np.float64)
        # worst case against the current decision
        worst_down = c_base - d_rho_dec * cnt_dec - err
        worst_up = c_base + d_rho_inc * cnt_inc + err

        log_ratio = np.log(cfg.alpha / cfg.beta)
        was_copy = state.copying
        # copying pairs stay decided if even the worst-case decrease keeps
        # them over θ_cp
        keep_copy = was_copy & (np.maximum(worst_down, worst_down.T)
                                >= cfg.theta_cp)
        # no-copying pairs stay decided if the worst-case increase keeps
        # them independent
        z_up = log_ratio + np.logaddexp(worst_up, worst_up.T)
        keep_ind = (~was_copy) & (z_up < 0.0)

        big_acc = np.abs(acc_new - state.acc_old) > rho_acc
        acc_flag = big_acc[:, None] | big_acc[None, :]

        candidates = state.considered & ~(keep_copy | keep_ind)
        candidates |= state.considered & acc_flag
        candidates &= np.triu(np.ones((S, S), bool), 1)
        n_cand = int(candidates.sum())
        trace.count("incremental.candidates", n_cand)
        n_considered = int(np.triu(state.considered, 1).sum())
        state.pass1_settled = 1.0 - n_cand / max(n_considered, 1)

    # ---- passes 2–3 collapsed: exact rescore of candidates ---------------
    c_fwd = c_base.astype(np.float32)
    pi, pj = np.nonzero(candidates)
    if rescore_pairs_exact(ds, p_claim, cfg, pi, pj, c_fwd):
        values_examined += int(state.l_counts[pi, pj].sum())
    np.fill_diagonal(c_fwd, 0.0)

    copying = decide_copying_np(c_fwd, c_fwd.T, cfg) & state.considered
    pr_ind = posterior_independence_np(c_fwd, c_fwd.T, cfg)
    pr_ind = np.where(state.considered, pr_ind, 1.0)
    np.fill_diagonal(pr_ind, 1.0)
    np.fill_diagonal(copying, False)

    # ---- fold updates back into the state ---------------------------------
    state.c_hat = c_fwd.copy()
    state.copying = copying.copy()
    state.p_old[big] = p_new[big]
    state.score_old[big] = score_new[big]
    state.acc_old[big_acc] = ds.accuracy[big_acc]
    if state.err is not None and len(pi):
        state.err = state.err.copy()
        state.err[pi, pj] = state.err[pj, pi] = 0.0   # rescored ⇒ now exact

    counter = ComputeCounter(
        pairs_considered=n_cand,
        shared_values_examined=values_examined,
        score_computations=2 * values_examined + 2 * n_cand,
        index_entries=E,
    )
    return DetectionResult(c_fwd=c_fwd, pr_independent=pr_ind, copying=copying,
                           counter=counter, wall_time_s=time.perf_counter() - t0)
