"""BOUND / BOUND+ / HYBRID (§IV) — early-terminating detection.

TPU adaptation (DESIGN.md §2.2): the paper terminates per pair mid-scan; we
terminate at *bucket* granularity. After each score-ordered bucket we evaluate
the paper's bounds for all active pairs at once:

  C^min = C⁰ + (l − n₀)·ln(1−s)                                  (Eq. 9)
  C^max = C⁰ + (h − n₀)·ln(1−s) + (l − h)·M                      (Eq. 10)
    h = clip(max(n(S1)·l/|D̄(S1)|, n(S2)·l/|D̄(S2)|), n₀, l)
    M = exact max score of the unscanned suffix (m_suffix)

and freeze pairs that cross θ_cp = ln β/α (copying) or fall below
θ_ind = ln β/2α (no-copying). Frozen pairs stop accumulating C⁰/n₀ (their
values at the decision point are what INCREMENTAL's bookkeeping needs),
while the total shared-value count n keeps counting (the paper's |Ē⋈|).

BOUND+ re-check timers (§IV-B) are implemented faithfully per pair: after a
failed copying check, C^min is not re-evaluated until n₀ grew by
T^min = ⌈(θ_cp − max C^min)/(M − ln(1−s))⌉; after a failed no-copying check,
C^max is not re-evaluated until (h − n₀) grew by T₀^max.

HYBRID applies bounds only to pairs sharing more than ``l_threshold`` items
(default 16, the paper's empirical crossover).

The scan STREAMS buckets out of the chunked ``CorpusStore`` (DESIGN.md §6):
one jitted per-bucket step is driven from the host, each step gathering only
its bucket's entry columns — the ``(K, S, w)`` bucket tensor of the legacy
``pad_buckets`` path is never materialized. Where the store is a plain
``CorpusStore`` whose copy fits the device, its incidence is shipped once a
call (``devchunks.upload``) and both reads come from that copy: the
``considered`` co-occurrence is one int8 matmul with int32 counts, and each
step slices its bucket out of it on the device. A row-sharded store, or one whose
copy does not fit, is read on the host (DESIGN.md §2.2).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import SingleDeviceSharding

from repro.core import devchunks
from repro.core.index import (
    BucketedIndex,
    InvertedIndex,
    bucketize,
    build_index,
    canonicalized,
)
from repro.core.scoring import (
    bucket_score_deltas,
    decide_copying,
    posterior_independence,
    rescore_pairs_exact,
    score_same,
)
from repro.core.store import CorpusStore
from repro.core.types import ClaimsDataset, CopyConfig, DetectionResult
from repro.utils import trace
from repro.utils.counters import ComputeCounter


@dataclass
class BoundState:
    """Post-scan per-pair state (all (S,S) numpy), consumed by INCREMENTAL."""

    c0: np.ndarray             # C⁰→ at decision point (== final for undecided)
    n0: np.ndarray             # shared values seen at decision point
    n_full: np.ndarray         # total shared values (all buckets)
    decided: np.ndarray        # int8: +1 copying, −1 no-copying, 0 till Step IV
    dec_bucket: np.ndarray     # bucket index of the decision (K if undecided)
    considered: np.ndarray     # co-occur outside Ē
    c_hat: np.ndarray          # Ĉ→ = C⁰_dec + (l − n)·ln(1−s)  (§V preparation)
    err: np.ndarray = None     # Σ δ_k·count accumulated p̂-error bound on C⁰→


@partial(jax.jit, static_argnames=("s", "n", "theta_cp", "theta_ind",
                                   "ln1ms", "use_timers", "K"))
def _bound_step(carry, v_k, p_k, m_next, delta_k, k, acc, l_counts, d_src,
                considered, boundable, s, n, theta_cp, theta_ind, ln1ms,
                use_timers, K):
    """One score-ordered bucket of the BOUND scan (Eqs. 9–10 + timers).

    ``v_k`` is the bucket's (S, w) incidence slice, zero-padded to the fixed
    maximum bucket width so every step reuses one compiled program. The
    carry threads the legacy 10-tuple plus the ``err`` accumulator:
    Σ δ_k·count bounds |C⁰ − C⁰_exact| (the p̂ approximation), and every
    freeze must now hold BEYOND the pair's accumulated error — which makes
    frozen decisions provably equal the exact INDEX for any bucketing,
    including a committed index's base+delta layout (DESIGN.md §7).
    """
    (c0, n0, n_full, nscan, decided, dec_bucket, min_due, max_due,
     err, ve, bc) = carry
    f_a1 = acc[:, None]
    f_a2 = acc[None, :]
    lf = l_counts.astype(jnp.float32)

    count = jnp.dot(v_k, v_k.T, preferred_element_type=jnp.float32)
    active = (decided == 0) & considered
    f = score_same(p_k, f_a1, f_a2, s, n)

    upd = active.astype(jnp.float32) * count
    c0 = c0 + f * upd
    n0 = n0 + upd
    err = err + delta_k * upd
    n_full = n_full + count * considered
    nscan = nscan + jnp.sum(v_k, axis=1)
    ve = ve + jnp.sum(jnp.triu(upd, 1))

    # ---- bounds (Eqs. 9–10), tightened by the accumulated p̂ error ----
    c_min_f = c0 - err + (lf - n0) * ln1ms
    c_min = jnp.maximum(c_min_f, c_min_f.T)
    h_raw = jnp.maximum(
        nscan[:, None] * lf / jnp.maximum(d_src[:, None], 1.0),
        nscan[None, :] * lf / jnp.maximum(d_src[None, :], 1.0),
    )
    h = jnp.clip(h_raw, n0, lf)
    c_max_f = c0 + err + (h - n0) * ln1ms + (lf - h) * m_next
    c_max = jnp.maximum(c_max_f, c_max_f.T)

    checkable = active & boundable
    if use_timers:
        check_min = checkable & (n0 >= min_due)
        check_max = checkable & ((h - n0) >= max_due)
    else:
        check_min = checkable
        check_max = checkable
    bc = bc + jnp.sum(jnp.triu(check_min, 1)) + jnp.sum(jnp.triu(check_max, 1))

    cp = check_min & (c_min >= theta_cp)
    ind = check_max & (c_max < theta_ind) & (c_max.T < theta_ind) & ~cp

    if use_timers:
        denom = jnp.maximum(m_next - ln1ms, 1e-6)
        t_min = jnp.ceil((theta_cp - c_min) / denom)
        min_due = jnp.where(check_min & ~cp, n0 + t_min, min_due)
        t0_max = jnp.ceil((c_max - theta_ind) / denom)
        max_due = jnp.where(check_max & ~ind, (h - n0) + t0_max, max_due)

    newly = jnp.where(cp, 1, jnp.where(ind, -1, 0)).astype(jnp.int8)
    decided = jnp.where((decided == 0) & (newly != 0), newly, decided)
    dec_bucket = jnp.where((dec_bucket == K) & (newly != 0), k, dec_bucket)

    return (c0, n0, n_full, nscan, decided, dec_bucket,
            min_due, max_due, err, ve, bc)


def _resident_incidence(store, S: int):
    """``store``'s incidence on the default device, entry ``e`` in row ``e``
    (``devchunks.upload``), or None where it stays on the host: a row-sharded
    store, or a copy that does not fit. Counts the bytes shipped under
    ``bound.h2d_bytes``."""
    if not isinstance(store, CorpusStore) or not store.n_chunks:
        return None
    dev = jax.devices()[0]
    # the copy and an upload batch, at most as much again for the masked
    # operand of the co-occurrence, and its (S, S) int32 counts
    need = 2 * devchunks.device_nbytes(store, S) + 4 * S * S
    if not devchunks.fits([dev], need):
        return None
    resident, nbytes = devchunks.upload(store, S, SingleDeviceSharding(dev))
    trace.count("bound.h2d_bytes", nbytes)
    return resident


@partial(jax.jit, static_argnames=("w", "dtype"))
def _bucket_slab(resident, s0, s1, w, dtype):
    """Entries ``[s0, s1)`` of the resident copy as an ``(S, w)`` slab, zero
    elsewhere. The slice's start is clamped so that it ends inside the copy,
    which puts the bucket's columns later in the slab; the step only sums
    over the columns, in exact integers, so their place does not matter."""
    start = jnp.minimum(s0, resident.shape[0] - w)
    e = start + jnp.arange(w)
    v = lax.dynamic_slice_in_dim(resident, start, w)
    v = jnp.where(((e >= s0) & (e < s1))[:, None], v, jnp.int8(0))
    return v.T.astype(dtype)


@jax.jit
def _considered(resident, keep):
    """Pairs that share an entry ``keep`` marks, off the diagonal. The
    counts are int32 sums of the int8 incidence, so the test is exact."""
    v = jnp.where(keep[:, None], resident, jnp.int8(0))
    n = lax.dot_general(v, resident, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
    return (n > 0) & ~jnp.eye(n.shape[0], dtype=bool)


def _bound_stream(idx: InvertedIndex, b: BucketedIndex, acc, l_counts, d_src,
                  considered, boundable, cfg: CopyConfig, use_timers: bool,
                  resident=None):
    """Drive the per-bucket step over buckets streamed from the store.

    Takes one bucket's columns at a time — out of ``resident`` on the
    device where given, else from the host (``store.slice_entries``) — so
    the scan never holds more than one bucket slice, not (K, S, w).
    """
    S = idx.n_sources
    K = b.n_buckets
    starts = b.starts
    w = int(max(np.diff(starts))) if K else 1
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32

    # δ_k per bucket — bounds the p̂ approximation of every accumulated score
    # term (scoring.bucket_score_deltas; p extremes live-masked by bucketize)
    p_lo = b.p_lo if b.p_lo is not None else b.p_hat
    p_hi = b.p_hi if b.p_hi is not None else b.p_hat
    deltas = bucket_score_deltas(b.p_hat, p_lo, p_hi, acc, cfg) if K else \
        np.zeros(0, np.float32)

    zero = jnp.zeros((S, S), jnp.float32)
    carry = (zero, zero, zero, jnp.zeros((S,), jnp.float32),
             jnp.zeros((S, S), jnp.int8), jnp.full((S, S), K, jnp.int32),
             zero, zero, zero,
             jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    accj = jnp.asarray(acc, jnp.float32)
    lj = jnp.asarray(l_counts)
    dj = jnp.asarray(d_src, jnp.float32)
    cj = jnp.asarray(considered)
    bj = jnp.asarray(boundable)
    for k in range(K):
        s0, s1 = int(starts[k]), int(starts[k + 1])
        if resident is None:
            v_np = np.zeros((S, w), np.float32)
            v_np[:, : s1 - s0] = idx.store.slice_entries(s0, s1,
                                                         dtype=np.float32)
            v_k = jnp.asarray(v_np, dt)
        else:
            v_k = _bucket_slab(resident, np.int32(s0), np.int32(s1), w=w,
                               dtype=dt)
        carry = _bound_step(
            carry, v_k, jnp.float32(b.p_hat[k]),
            jnp.float32(b.m_suffix[k + 1]), jnp.float32(deltas[k]),
            jnp.int32(k), accj, lj, dj, cj, bj,
            s=cfg.s, n=cfg.n, theta_cp=cfg.theta_cp, theta_ind=cfg.theta_ind,
            ln1ms=cfg.ln_1ms, use_timers=use_timers, K=K)
    return carry


@trace.spanned("engine.bound")
def bound_detect(
    ds: ClaimsDataset,
    p_claim: np.ndarray,
    cfg: CopyConfig,
    n_buckets: int = 64,
    use_timers: bool = False,          # False = BOUND, True = BOUND+
    l_threshold: int = 0,              # >0 = HYBRID (INDEX for small-overlap pairs)
    rescore_margin: float = 1.0,
    index: InvertedIndex | None = None,
    bucketed: BucketedIndex | None = None,
    return_state: bool = False,
):
    """BOUND (§IV-A), BOUND+ (§IV-B, use_timers), HYBRID (l_threshold=16)."""
    t0 = time.perf_counter()
    idx = index if index is not None else build_index(ds, p_claim, cfg)
    if bucketed is None:
        # a committed index is re-gathered into score-sorted prefix-Ē form
        # first, so the bucket geometry (and Eq. 10's scan-order-dependent h
        # estimate) matches a from-scratch rebuild exactly (DESIGN.md §7);
        # callers that pass their own ``bucketed`` keep the physical order
        idx = canonicalized(idx, cfg)
        bucketed = bucketize(idx, n_buckets)
    S = ds.n_sources
    K = bucketed.n_buckets
    l_counts = idx.l_counts
    d_src = idx.items_per_source

    # considered = co-occurrence outside Ē: exact integer counts on either
    # path (int32 on the device; 0/1 products in f32 chunk by chunk on the
    # host), so both give the same mask; the mask form covers committed
    # indexes, where Ē is no longer a physical suffix (DESIGN.md §7)
    with trace.span("bound.considered") as sp:
        resident = _resident_incidence(idx.store, S)
        if resident is None:
            considered = idx.store.cooccurrence(mask=idx.nonebar_mask) > 0.5
            np.fill_diagonal(considered, False)
        else:
            keep = np.zeros(resident.shape[0], bool)
            keep[: idx.n_entries] = idx.nonebar_mask
            considered = np.array(_considered(resident, keep))
        sp.set(incidence="host" if resident is None else "device")

    boundable = idx.l_counts > l_threshold
    np.fill_diagonal(boundable, False)

    with trace.span("bound.stream", buckets=K):
        (c0, n0, n_full, _nscan, decided, dec_bucket, _md, _xd, err, ve,
         bc) = _bound_stream(idx, bucketed, ds.accuracy, l_counts, d_src,
                             considered, boundable, cfg, use_timers, resident)
        del resident                # free the copy before the rescore
        c0, n0 = np.array(c0), np.array(n0)
        n_full = np.array(n_full)
        decided = np.array(decided)
        dec_bucket = np.array(dec_bucket)
        err = np.array(err)

    lf = idx.l_counts.astype(np.float32)
    # Step IV for still-active pairs (n0 == n_full there): C→ = C^min
    c_fwd = np.where(considered, c0 + (lf - n0) * cfg.ln_1ms, 0.0).astype(np.float32)
    np.fill_diagonal(c_fwd, 0.0)

    # Ĉ for incremental bookkeeping (§V preparation step)
    c_hat = np.where(considered, c0 + (lf - n_full) * cfg.ln_1ms, 0.0).astype(np.float32)

    active = (decided == 0) & considered
    z = np.log(cfg.alpha / cfg.beta) + np.logaddexp(c_fwd, c_fwd.T)
    # a still-active pair's decision can only differ from the exact INDEX if
    # the accumulated p̂ error reaches its decision margin — widen the band
    # by it, exactly as the engine's §3.4 rescore does
    near = (active
            & (np.abs(z) < rescore_margin + np.maximum(err, err.T))
            & np.triu(np.ones((S, S), bool), 1))
    pi, pj = np.nonzero(near)
    rescore_pairs_exact(ds, p_claim, cfg, pi, pj, c_fwd)

    step4 = np.array(decide_copying(jnp.asarray(c_fwd), jnp.asarray(c_fwd.T), cfg))
    copying = np.where(decided != 0, decided > 0, step4) & considered
    pr_ind = np.array(posterior_independence(jnp.asarray(c_fwd), jnp.asarray(c_fwd.T), cfg))
    pr_ind = np.where(considered, pr_ind, 1.0)
    pr_ind = np.where(decided > 0, np.minimum(pr_ind, 0.5), pr_ind)
    pr_ind = np.where(decided < 0, np.maximum(pr_ind, 0.5), pr_ind)
    np.fill_diagonal(pr_ind, 1.0)
    np.fill_diagonal(copying, False)

    iu = np.triu_indices(S, 1)
    n_pairs = int(considered[iu].sum())
    counter = ComputeCounter(
        pairs_considered=n_pairs,
        shared_values_examined=int(ve),
        score_computations=2 * int(ve) + 2 * n_pairs + 2 * len(pi),
        bound_computations=2 * int(bc),
        index_entries=idx.n_entries,
    )
    result = DetectionResult(c_fwd=c_fwd, pr_independent=pr_ind, copying=copying,
                             counter=counter, wall_time_s=time.perf_counter() - t0)
    if return_state:
        state = BoundState(c0=c0, n0=n0, n_full=n_full, decided=decided,
                           dec_bucket=dec_bucket, considered=considered,
                           c_hat=c_hat, err=err)
        return result, state
    return result


def hybrid_detect(ds, p_claim, cfg, n_buckets: int = 64, **kw):
    """HYBRID: INDEX semantics for pairs sharing ≤16 items, BOUND+ beyond."""
    return bound_detect(ds, p_claim, cfg, n_buckets=n_buckets,
                        use_timers=True, l_threshold=16, **kw)
