"""Iterative truth finding with copy-aware vote discounting (§II, [6]).

Each round: (1) copy detection → Pr(copy) per pair; (2) value-probability
computation where each source's vote is discounted by the probability that it
provided the value independently; (3) source-accuracy update. Repeat until
accuracies converge (the motivating example converges in 5 rounds, Table II).

Vote model (ACCU of Dong et al. [6], vectorized):
  vote weight      σ_s = ln(n·A_s / (1−A_s))
  independence     I_{s,e} = Π_{t ∈ S̄(e), (A_t,t) ≻ (A_s,s)} (1 − c·Pr(copy)[s,t])
                   (each provider discounted by higher-accuracy co-providers,
                    the paper's ordering trick to count each pair once)
  value vote       vote_e = Σ_{s ∈ S̄(e)} σ_s · I_{s,e}
  probability      P(e) = e^{vote_e} / (Σ_{e' ∈ item(e)} e^{vote_e'} + n₀·e⁰)
                   with n₀ = max(n − |observed values|, 0) unobserved false
                   values at vote 0
  accuracy         A_s = mean_e∈claims(s) P(e), clipped to [.01, .99]

The independence matmul (L ⊙ H) @ V_all is MXU work — see DESIGN.md §2.
Each call ships the value groups to the device once; every round's vote
then runs ``kernels/vote.py`` over the resident int8 incidence, with the
(S, E_all) log-independence folded into the votes in VMEM.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import DetectionEngine
from repro.core.types import ClaimsDataset, CopyConfig, DetectionResult
from repro.kernels.ref import vote_fused_ref
from repro.kernels.vote import split3, vote_fused_pallas
from repro.utils import trace


# ---------------------------------------------------------------------------
# Value groups: one entry per (item, value) INCLUDING singletons
# ---------------------------------------------------------------------------

@dataclass
class ValueGroups:
    """All distinct (item, value) claims, for vote computation."""

    V_all: np.ndarray        # (S, E_all) uint8
    entry_item: np.ndarray   # (E_all,)
    claim_entry: np.ndarray  # (S, D) int32 — entry id of each claim, −1 missing
    n_values_per_item: np.ndarray  # (D,)


def build_value_groups(ds: ClaimsDataset) -> ValueGroups:
    """Group every claim by (item, value) — singletons included.

    Unlike the inverted index (shared values only, §III), truth finding
    votes over ALL distinct values, so this builds the full (S, E_all)
    incidence plus the (S, D) claim→entry map used to expand entry
    probabilities back to per-claim probabilities each round. Entries are
    ordered by (item, value); only the provided claims are keyed, never the
    whole (S, D) grid."""
    values = ds.values
    S, D = values.shape
    rows, cols = np.nonzero(values >= 0)
    vals = values[rows, cols].astype(np.int64)
    max_v = int(vals.max()) + 1 if vals.size else 1
    uniq, inv = np.unique(cols.astype(np.int64) * max_v + vals,
                          return_inverse=True)
    claim_entry = np.full((S, D), -1, np.int32)
    claim_entry[rows, cols] = inv
    V_all = np.zeros((S, len(uniq)), dtype=np.uint8)
    V_all[rows, inv] = 1
    entry_item = (uniq // max_v).astype(np.int32)
    n_vals = np.bincount(entry_item, minlength=D).astype(np.int32)
    return ValueGroups(V_all=V_all, entry_item=entry_item,
                       claim_entry=claim_entry, n_values_per_item=n_vals)


# ---------------------------------------------------------------------------
# One fusion round
# ---------------------------------------------------------------------------

def _normalize(votes, entry_item, n_vals_per_item, n, n_items):
    """P(e): per-item softmax of the votes, with the n₀ unobserved false
    values of each item at vote 0."""
    seg_max = jax.ops.segment_max(votes, entry_item, num_segments=n_items)
    seg_max = jnp.maximum(seg_max, 0.0)                          # include e⁰ mass
    ex = jnp.exp(votes - seg_max[entry_item])
    denom_obs = jax.ops.segment_sum(ex, entry_item, num_segments=n_items)
    n_unobs = jnp.maximum(n - n_vals_per_item.astype(jnp.float32), 0.0)
    denom = denom_obs + n_unobs * jnp.exp(-seg_max)
    return ex / denom[entry_item]


@partial(jax.jit, static_argnames=("n", "c", "n_items"))
def vote_round_reference(V_all, entry_item, acc, pr_copy, n, c, n_items,
                         n_vals_per_item):
    """→ (entry probability P(e), new accuracy A): the plain dense round,
    float32 at the highest matmul precision, with no kernel. Tests compare
    ``vote_round`` with it; it holds (S, E_all) float32 arrays, so it is
    for small corpora only."""
    with jax.default_matmul_precision("highest"):
        S = acc.shape[0]
        sigma = jnp.log(n * acc / (1.0 - acc))                   # (S,)
        # H[s,t] = 1 iff provider t ranks above s (accuracy, index tiebreak)
        rank = acc * S + jnp.arange(S, dtype=acc.dtype)          # strict total order
        H = (rank[None, :] > rank[:, None]).astype(jnp.float32)
        L = jnp.log1p(-jnp.clip(c * pr_copy, 0.0, 0.999))        # ln(1 − c·Pcp)
        V = V_all.astype(jnp.float32)
        logI = jnp.dot(L * H, V)                                 # (S, E_all)
        votes = jnp.sum(V * sigma[:, None] * jnp.exp(logI), axis=0)
        p_entry = _normalize(votes, entry_item, n_vals_per_item, n, n_items)
        claims_per_src = jnp.maximum(jnp.sum(V, axis=1), 1.0)
        new_acc = jnp.dot(V, p_entry) / claims_per_src
    return p_entry, jnp.clip(new_acc, 0.01, 0.99)


#: Entry (value-group) block of the vote kernel; the resident incidence's
#: entry axis is padded to a multiple of it.
VOTE_BLOCK_E = 512
#: Largest source block of the vote kernel (a multiple of 128).
VOTE_MAX_BLOCK_S = 640


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _vote_block_s(s_pad: int) -> int:
    """The largest multiple of 128 up to ``VOTE_MAX_BLOCK_S`` dividing
    ``s_pad`` (a multiple of 128)."""
    return max(b for b in range(128, min(s_pad, VOTE_MAX_BLOCK_S) + 1, 128)
               if s_pad % b == 0)


@dataclass
class ResidentGroups:
    """One ``truth_finding`` call's value groups on the device, uploaded
    once (``upload_groups``) and read by every round's ``vote_round``."""

    v: jax.Array             # (S_pad, E_pad) int8 incidence, zero-padded
    entry_item: jax.Array    # (E_all,) int32
    n_vals: jax.Array        # (D,) int32 observed values per item
    claim_src: jax.Array     # (C,) int32 source of each claim
    claim_ent: jax.Array     # (C,) int32 entry of each claim
    n_claims: jax.Array      # (S,) float32 claims per source, at least 1
    rows: np.ndarray         # (C,) host copies of the claims' coordinates,
    cols: np.ndarray         #      for expanding P(e) to (S, D) claims
    ents: np.ndarray


def upload_groups(groups: ValueGroups) -> ResidentGroups:
    """Ship ``groups`` to the default device: the incidence as int8, padded
    to the vote kernel's blocks on the device, and the claims as index
    lists. Counts the bytes shipped under ``truth.h2d_bytes``."""
    S, E = groups.V_all.shape
    rows, cols = np.nonzero(groups.claim_entry >= 0)
    ents = groups.claim_entry[rows, cols]
    n_claims = np.maximum(np.bincount(rows, minlength=S), 1).astype(np.float32)
    host = [groups.V_all, groups.entry_item, groups.n_values_per_item,
            rows.astype(np.int32), ents, n_claims]
    trace.count("truth.h2d_bytes", sum(a.nbytes for a in host))
    v, *rest = jax.device_put(host)
    v = _pad_incidence(v, _round_up(S, 128), _round_up(E, VOTE_BLOCK_E))
    return ResidentGroups(v, *rest, rows=rows, cols=cols, ents=ents)


@partial(jax.jit, static_argnums=(1, 2))
def _pad_incidence(v, s_pad, e_pad):
    return jnp.pad(v.astype(jnp.int8),
                   ((0, s_pad - v.shape[0]), (0, e_pad - v.shape[1])))


def vote_weights(acc: np.ndarray, pr_copy: np.ndarray, c: float, n: float):
    """(σ, L ⊙ H) of a round on the host, float32, as
    ``vote_round_reference`` defines them: σ_s = ln(n·A_s/(1−A_s)) and
    (L ⊙ H)[s,t] = ln(1 − c·Pr(copy)[s,t]) where t ranks above s."""
    acc = np.asarray(acc, np.float32)
    S = acc.shape[0]
    sigma = np.log(np.float32(n) * acc / (np.float32(1) - acc))
    rank = acc * np.float32(S) + np.arange(S, dtype=np.float32)
    lh = np.log1p(-np.clip(np.float32(c) * np.asarray(pr_copy, np.float32),
                           np.float32(0), np.float32(0.999)))
    lh[rank[None, :] <= rank[:, None]] = 0.0
    return sigma, lh


@partial(jax.jit, static_argnames=("n", "impl"))
def _vote_device(v, entry_item, n_vals, claim_src, claim_ent, n_claims,
                 sigma, lh, *, n, impl):
    s_pad = v.shape[0]
    S = sigma.shape[0]
    lh3 = split3(jnp.pad(lh, ((0, s_pad - S), (0, s_pad - S))))
    sig = jnp.pad(sigma, (0, s_pad - S)).reshape(s_pad, 1)
    if impl == "ref":
        votes = vote_fused_ref(lh3, v, sig, block_e=VOTE_BLOCK_E)
    else:
        votes = vote_fused_pallas(lh3, v, sig, block_s=_vote_block_s(s_pad),
                                  block_e=VOTE_BLOCK_E,
                                  interpret=impl == "interpret")
    votes = votes[0, : entry_item.shape[0]]
    p_entry = _normalize(votes, entry_item, n_vals, n, n_vals.shape[0])
    acc = jax.ops.segment_sum(p_entry[claim_ent], claim_src,
                              num_segments=S) / n_claims
    return p_entry, jnp.clip(acc, 0.01, 0.99)


def vote_round(res: ResidentGroups, acc: np.ndarray, pr_copy: np.ndarray,
               cfg: CopyConfig, impl: str = "auto"):
    """One round's vote over resident groups → (P(e), new accuracies), host
    float32. The votes come from ``vote_fused_pallas`` (``impl``: auto —
    the kernel on a TPU, its blocked jnp oracle elsewhere — pallas,
    interpret or ref); no (S, E_all) float32 array is made. Counts the
    round's (S, S) upload under ``truth.h2d_bytes``."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    sigma, lh = vote_weights(acc, pr_copy, cfg.c, cfg.n)
    trace.count("truth.h2d_bytes", sigma.nbytes + lh.nbytes)
    s_pad, e_pad = res.v.shape
    trace.annotate(impl=impl, s_pad=s_pad, e_pad=e_pad, sources=len(sigma),
                   e_all=int(res.entry_item.shape[0]))
    p_entry, new_acc = _vote_device(
        res.v, res.entry_item, res.n_vals, res.claim_src, res.claim_ent,
        res.n_claims, sigma, lh, n=float(cfg.n), impl=impl)
    return np.asarray(p_entry), np.asarray(new_acc)


def claim_probabilities(res: ResidentGroups, p_entry: np.ndarray,
                        shape) -> np.ndarray:
    """(S, D) float32 P of each provided claim's value, 0 where none."""
    p_claim = np.zeros(shape, np.float32)
    p_claim[res.rows, res.cols] = p_entry[res.ents]
    return p_claim


# ---------------------------------------------------------------------------
# The iterative driver
# ---------------------------------------------------------------------------

# every detector is a DetectionEngine mode — the engine is the single entry
# point for detection compute (DESIGN.md §3); keyword args go to EngineOptions
_ENGINE_MODE = {
    "pairwise": "pairwise",
    "index_exact": "exact",
    "index": "bucketed",
    "bound": "bound",
    "bound+": "bound+",
    "hybrid": "hybrid",
}


def _engine_detector(mode: str) -> Callable:
    def run(ds, p_claim, cfg, **kw):
        return DetectionEngine(cfg, mode=mode, **kw).detect(ds, p_claim)
    return run


DETECTORS: dict[str, Callable] = {
    name: _engine_detector(mode) for name, mode in _ENGINE_MODE.items()
}


@dataclass
class FusionResult:
    """Converged truth-finding state plus per-round history/diagnostics.

    With ``track_history`` the histories hold every round, round 0 (the
    vote without copy knowledge) first: ``accuracy_history[r]`` and
    ``p_history[r]`` are round r's vote outputs, and ``detections[r - 1]``
    is the detection of round r ≥ 1, which ran on ``accuracy_history[r -
    1]`` and ``p_history[r - 1]``."""

    accuracy: np.ndarray            # (S,) final accuracies
    p_entry: np.ndarray             # (E_all,) final value probabilities
    p_claim: np.ndarray             # (S, D) final claim probabilities
    groups: ValueGroups
    detection: DetectionResult
    rounds: int = 0
    accuracy_history: list = field(default_factory=list)
    p_history: list = field(default_factory=list)
    detections: list = field(default_factory=list)
    counters: list = field(default_factory=list)
    wall_time_s: float = 0.0
    detect_time_s: float = 0.0


def truth_finding(
    ds: ClaimsDataset,
    cfg: CopyConfig,
    detector: str | Callable = "hybrid",
    max_rounds: int = 12,
    tol: float = 5e-4,
    init_accuracy: float = 0.8,
    detector_kwargs: Optional[dict] = None,
    track_history: bool = False,
) -> FusionResult:
    """Iterative copy detection + truth finding + accuracy update (§II-A).

    One span ``truth.run`` per call, holding ``truth.value_groups`` (the
    groups built and made resident once) and one ``truth.vote`` per round;
    counters ``truth.h2d_bytes`` and ``truth.rounds`` (detection rounds run).
    """
    t0 = time.perf_counter()
    with trace.span("truth.run", detector=str(detector)):
        kw = dict(detector_kwargs or {})
        inc_engine = None
        if detector == "incremental":
            detect = None
            inc_engine = DetectionEngine(cfg, mode="incremental", **kw)
        else:
            detect = DETECTORS[detector] if isinstance(detector, str) \
                else detector
        with trace.span("truth.value_groups"):
            groups = build_value_groups(ds)
            resident = upload_groups(groups)
        S, D = ds.values.shape

        def vote(rnd, acc, pr_copy):
            with trace.span("truth.vote", round=rnd):
                return vote_round(resident, acc, pr_copy, cfg)

        # round 0: no copy knowledge yet — votes with Pr(copy)=0
        p_entry, acc_np = vote(0, np.full(S, init_accuracy, np.float32),
                               np.zeros((S, S), np.float32))
        history, p_hist, detections, counters = [], [], [], []
        if track_history:
            history.append(acc_np.copy())
            p_hist.append(p_entry.copy())
        detection = None
        detect_time = 0.0

        for rnd in range(1, max_rounds + 1):
            work = ClaimsDataset(values=ds.values, accuracy=acc_np)
            p_claim = claim_probabilities(resident, p_entry, (S, D))
            td0 = time.perf_counter()
            if inc_engine is not None:
                # §V: HYBRID in the first round; round 2 bootstraps the
                # engine's incremental bookkeeping, later rounds apply
                # per-round deltas
                if rnd < 2:
                    detection = DetectionEngine(cfg, mode="hybrid",
                                                **kw).detect(work, p_claim)
                else:
                    detection = inc_engine.detect(work, p_claim)
            else:
                detection = detect(work, p_claim, cfg, **kw)
            detect_time += time.perf_counter() - td0
            counters.append(detection.counter)
            pr_copy = (1.0 - detection.pr_independent).astype(np.float32)

            p_entry, new_acc = vote(rnd, acc_np, pr_copy)
            if track_history:
                history.append(new_acc.copy())
                p_hist.append(p_entry.copy())
                detections.append(detection)
            delta = float(np.max(np.abs(new_acc - acc_np)))
            acc_np = new_acc
            if delta < tol:
                break
        trace.count("truth.rounds", rnd)

        p_claim = claim_probabilities(resident, p_entry, (S, D))
    return FusionResult(
        accuracy=acc_np, p_entry=p_entry, p_claim=p_claim,
        groups=groups, detection=detection, rounds=rnd,
        accuracy_history=history, p_history=p_hist, detections=detections,
        counters=counters, wall_time_s=time.perf_counter() - t0,
        detect_time_s=detect_time,
    )


def fusion_accuracy(result: FusionResult, ds: ClaimsDataset,
                    true_values: np.ndarray) -> float:
    """Fraction of items whose top-probability value is the true one."""
    D = ds.n_items
    best = np.full(D, -1, np.int64)
    best_p = np.full(D, -np.inf)
    for e in range(len(result.p_entry)):
        d = result.groups.entry_item[e]
        if result.p_entry[e] > best_p[d]:
            best_p[d] = result.p_entry[e]
            best[d] = e
    # map entry back to a value id via any provider
    correct = 0
    total = 0
    V = result.groups.V_all
    for d in range(D):
        if best[d] < 0:
            continue
        providers = np.nonzero(V[:, best[d]])[0]
        if providers.size == 0:
            continue
        v = ds.values[providers[0], d]
        total += 1
        correct += int(v == true_values[d])
    return correct / max(total, 1)
