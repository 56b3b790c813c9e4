"""DetectionEngine — the single entry point for scalable copy detection.

Every detection workload (one-shot exact, production bucketed, bound /
hybrid early termination, iterative incremental rounds, sampled detection)
goes through ``DetectionEngine.detect``. The production ``bucketed`` mode is
the sharded, pair-tiled dataflow of DESIGN.md §3:

  1. build the inverted index (§III — streamed into the chunked
     ``CorpusStore``, never a dense (S, E) array) and re-chunk it p-sorted
     on each side of the Ē boundary (``engine_order`` — the accumulation
     is order-insensitive, so p-homogeneous chunks shrink the p̂ error;
     chunks double as the kernel's entry blocks);
  2. cut the S×S pair space into T×T tiles and prune, up front, every tile
     whose sources co-occur only inside the low-contribution suffix Ē — by
     Proposition 3.4 those pairs can never flip to copying, so the whole
     tile is skipped without touching a device (the tile-level test uses the
     OR-reduced incidence, an upper bound on any pair's co-occurrence); the
     keep matrix is symmetric, so only unordered (r ≤ c) tiles survive —
     the triangular schedule halves the tiles scheduled. The OR-reduction
     is kept per chunk, so tile pruning composes with chunk pruning
     (DESIGN.md §6);
  3. stream chunk GROUPS (default one chunk per device pass; an optional
     byte budget groups chunks for dispatch-bound meshes) over a 1-D
     device mesh (shard_map) — each group's slab gathered on the device
     from the base incidence shipped once per pass (``devchunks``), or on
     the host for a row-sharded store; each device scans its surviving
     tiles, slicing the int8 chunk slab and feeding the fused
     dual-direction copyscore kernel one unordered tile at a time — one
     count matmul per entry block emits C→, C←, the shared count, the
     non-Ē count, and the error bound; per-tile accumulators stay on
     device across groups;
  4. scatter both orientations of every tile back into (S, S) (C← transposed
     lands at the mirrored coordinate), apply the INDEX step-3
     different-value adjustment, exactly rescore every pair whose decision
     margin is within its accumulated error bound, and decide — binary
     decisions match ``index_detect_exact`` (asserted by the engine tests
     and cross-checked by the scaling benchmark on every run).

Modes
  pairwise      exhaustive oracle (§II-B)
  exact         entry-sequential INDEX with paper-metric accounting (§III)
  bucketed      tiled + sharded production INDEX (this module)
  bound/bound+  early-terminating BOUND, optionally with timers (§IV)
  hybrid        BOUND+ for pairs sharing > l_threshold items (§IV-C)
  incremental   stateful rounds: first call bootstraps HYBRID + bookkeeping,
                later calls apply per-round deltas (§V)
  sampled       item sampling (§VI) then the tiled path on the subset
  sample_verify SCALESAMPLE candidate discovery, then an exact gathered
                rescore of only the candidate pairs — decisions on the
                candidate set equal ``index_detect_exact`` (DESIGN.md §4)
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import devchunks, tilecache
from repro.core.bound import bound_detect
from repro.core.bucketed import index_detect_exact
from repro.core.distributed import sharded_tile_scores, sharded_tile_scores_2d
from repro.core.pipeline import ChunkPrefetcher, PipelineStageError
from repro.core.incremental import incremental_detect, make_incremental_state
from repro.core.index import InvertedIndex, build_index, engine_order
from repro.core.sampling import sample_by_cell, sample_by_item, scale_sample
from repro.core.shardplan import (
    OwnerPartial,
    ShardScanError,
    ShardedCorpusStore,
    make_shard_plan,
    merge_owner_partials,
    merge_shard_partials,
    scatter_tile_stacks,
    shard_store,
)
from repro.core.scoring import (
    bucket_score_deltas,
    decide_copying_np,
    pairwise_detect,
    posterior_independence_np,
    rescore_pairs_exact,
)
from repro.core.types import ClaimsDataset, CopyConfig, DetectionResult
from repro.runtime.platform import keep_host_arrays_on_heap
from repro.utils import trace
from repro.utils.counters import ComputeCounter

MODES = ("pairwise", "exact", "bucketed", "bound", "bound+", "hybrid",
         "incremental", "sampled", "sample_verify")


@dataclass
class EngineOptions:
    """Tuning knobs; mode-specific fields are ignored by other modes."""

    # entry buckets per index (count). 64 keeps the within-bucket p̂ error —
    # and with it the rescore set — small while the bucket scan stays matmul-
    # bound (DESIGN.md §3.1).
    n_buckets: int = 64
    # pair-tile edge (sources per tile side). 256 divides into two 128-wide
    # MXU pair blocks; clamped down for tiny datasets (see _tile_edge).
    tile: int = 256
    # 1-D tile-mesh size (device count); None → every local device.
    devices: Optional[int] = None
    # decision-margin band (log-odds units) around z = 0 that triggers an
    # exact rescore on top of the accumulated p̂-error bound. 1.0 adds slack
    # for the float32 accumulation order; the bound itself carries the
    # approximation error (DESIGN.md §3.4).
    rescore_margin: float = 1.0
    # kernel dispatch: auto (Pallas on TPU, jnp reference elsewhere) |
    # pallas | interpret | ref.
    kernel_impl: str = "auto"
    # incidence element type: auto (→ int8; exact int32 MXU accumulation at
    # half the HBM traffic) | int8 | bf16 | f32 (microbenchmark ablations).
    incidence_dtype: str = "auto"
    # hybrid crossover: apply BOUND checks only to pairs sharing more than
    # this many items; None → 16, the paper's §IV-C empirical crossover.
    l_threshold: Optional[int] = None
    # sampled / sample_verify: fraction of item columns to keep (0..1].
    # 0.1 reproduces the paper's §VI operating point (Table IX).
    sample_rate: float = 0.1
    # sampling strategy: scale (SCALESAMPLE) | item (BYITEM) | cell (BYCELL).
    sample_strategy: str = "scale"
    # SCALESAMPLE floor (items per source): every source keeps ≥ this many
    # sampled items when it has them. 4 is the paper's N (§VI-E).
    min_per_source: int = 4
    # RNG seed for the item sample — fixed so detection runs are replayable.
    sample_seed: int = 1
    # incremental: |ΔM̂| (log-odds units) above which an entry is treated as
    # a big change and replayed exactly (§V-A; 1.0 ≈ the paper's ρ).
    rho: float = 1.0
    # incremental: |ΔA| accuracy drift that forces a pair rescore
    # unconditionally (fraction, 0..1). 0.2 is the paper's ρ_acc.
    rho_acc: float = 0.2
    # sample_verify: initial half-width (log-odds units, sampled-score scale)
    # of the candidate net below the copying boundary z = 0. 2.0 ≈ the
    # decision band where sampling noise plausibly hides a true pair.
    verify_slack: float = 2.0
    # sample_verify: multiplicative step of the recall-slack sweep (> 1).
    verify_slack_growth: float = 1.6
    # sample_verify: stop widening when the next shell of near-miss pairs
    # holds fewer than this fraction of the current candidate set — the
    # empirical bound on pairs the net might still miss.
    verify_miss_frac: float = 0.02
    # chunks of the engine store shipped per device pass (count). 1 (the
    # default) is strict streaming — peak resident incidence is ONE chunk —
    # and also measured fastest on CPU at S=2048 (8.5 s vs 13.3 s shipping
    # 63 chunks at once: the chunk-sized working set stays in cache). None →
    # auto-size from chunk_group_bytes, capped at K−1 so a chunked store's
    # full incidence is never resident in one allocation.
    chunk_group: Optional[int] = 1
    # HARD byte ceiling on the incidence slab shipped per device pass: it
    # narrows the engine chunk width when one n_buckets-derived chunk would
    # exceed it (floored at 8 entries × S_pad rows) and clamps any
    # requested/auto chunk_group. With chunk_group=None it doubles as the
    # auto group-size target for meshes where dispatch latency, not cache
    # locality, dominates.
    chunk_group_bytes: int = 64 << 20
    # canonical CorpusStore chunk width (entries) for indexes this engine
    # builds; None → store default (512). Rounded up to a multiple of 8.
    store_chunk_entries: Optional[int] = None
    # byte budget for the largest single incidence allocation during index
    # build (wins over store_chunk_entries; width = bytes // rows).
    store_chunk_bytes: Optional[int] = None
    # row-range shards of the corpus data plane (DESIGN.md §10). None/1 →
    # unsharded. Indexes this engine builds are wrapped in a
    # ShardedCorpusStore; each shard scans only the pair tiles whose ROW
    # block it owns (assembling just the row blocks those tiles touch) and
    # the per-shard partial grids merge — error channel by MAX — into
    # decisions bit-equal to the unsharded engine.
    n_shards: Optional[int] = None
    # bitpack each shard's chunk blocks to 1 bit/entry when the engine
    # store is sealed for the scan (8× over int8; unpacked per assembly).
    shard_pack: bool = False
    # per-shard resident-set byte cap: cold blocks spill to checksummed
    # frames (WAL container) under shard_spill_dir, LRU. None → no cap.
    shard_spill_bytes: Optional[int] = None
    # spill directory; None → a fresh temp dir when a byte cap is set.
    shard_spill_dir: Optional[str] = None
    # 2-D device mesh (data, pod) for the tile scan: tiles shard over
    # `data`, entry chunks over `pod`, one psum combines (DESIGN.md §10).
    # None → the 1-D tile mesh.
    mesh_shape: Optional[tuple] = None
    # chunk groups staged host→device AHEAD of the running kernel by the
    # async pipeline (DESIGN.md §11): a producer thread assembles and
    # transfers group G+1's v-slab while group G computes, double-buffered
    # at depth 2. 0 → fully synchronous staging (the pre-pipeline path);
    # stall telemetry (stage_wait_s / compute_wait_s) lands in last_stats
    # either way.
    prefetch_depth: int = 2


@dataclass
class TileScanContext:
    """The deterministic prologue of one tiled pass, reified (DESIGN.md §12).

    Everything the tile scans and the finalize step consume — resolved
    index, engine chunk store, bucket deltas, the tile∘chunk keep masks,
    the surviving unordered tile coords, group sizing — computed ONCE.
    ``_detect_tiled`` builds and consumes it inline; the shard-owner
    fan-out builds it once on the router's engine and hands the SAME
    context to every owner's ``detect_owner_partial``, so the per-owner
    scans see identical kernel operands and the merged decisions stay
    bit-equal to the single-host pass. For sampled modes ``ds``/``p_claim``
    are the item-subset views the scan runs over and ``items`` records the
    deterministic sample (``sample_seed``) for the verify stage.
    """

    t0: float
    ds: ClaimsDataset
    p_claim: np.ndarray
    base_idx: InvertedIndex
    ech: object                    # EngineChunks — p-ordered chunking
    delta: np.ndarray              # per-chunk p̂-error bound δ_k
    sharded: bool
    S: int
    T: int
    n_blocks: int
    S_pad: int
    acc_pad: np.ndarray
    block: int
    dtype: object                  # jnp incidence dtype
    chunk_keep: np.ndarray         # (K, n_blocks, n_blocks) bool
    coords: np.ndarray             # (n_tiles, 2) int32 — surviving r ≤ c tiles
    tiles_total: int
    n_tiles: int
    Gc: int                        # chunks per device pass
    chunk_nbytes: int
    resident_nbytes: int
    mask_source: str
    gather: str                    # "device" | "host" — where slabs are gathered
    resident: object = None        # device copy of the base incidence (device
                                   # gather), dropped when the scan ends
    items: Optional[np.ndarray] = None   # sampled/sample_verify item subset


class DetectionEngine:
    """One engine instance per detection workload.

    Stateless for one-shot modes; ``incremental`` carries the paper's §V
    bookkeeping across ``detect`` calls (``reset()`` drops it).
    """

    def __init__(self, cfg: CopyConfig, mode: str = "bucketed", **options):
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
        self.cfg = cfg
        self.mode = mode
        self.options = EngineOptions(**options)
        keep_host_arrays_on_heap()
        self.last_stats: dict = {}
        self._mesh: Optional[Mesh] = None
        self._mesh2: Optional[Mesh] = None
        self._inc_state = None
        self._last_considered: Optional[np.ndarray] = None
        # incremental block-OR mask cache (DESIGN.md §11): per-entry tile-
        # block incidence over the LAST persistent index this engine
        # detected against, delta-updated at commit/retract time
        self._mask_cache = None
        self._mask_cache_hits = 0
        self._mask_full_builds = 0
        # pipeline-stall telemetry accumulated across the current pass
        self._pipe = {"stage_wait_s": 0.0, "compute_wait_s": 0.0,
                      "staging_s": 0.0}

    # -- lifecycle ----------------------------------------------------------

    def reset(self) -> None:
        """Drop incremental bookkeeping (next detect() bootstraps afresh)."""
        self._inc_state = None

    @property
    def incremental_state(self):
        """§V bookkeeping (None until an incremental detect() has run)."""
        return self._inc_state

    def mesh(self) -> Mesh:
        """The 1-D tile mesh (built lazily so XLA_FLAGS can be set first)."""
        if self._mesh is None:
            n = self.options.devices or len(jax.devices())
            self._mesh = Mesh(np.array(jax.devices()[:n]), ("shards",))
        return self._mesh

    def mesh2(self) -> Mesh:
        """The 2-D ``data``×``pod`` tile mesh (``mesh_shape`` option)."""
        if self._mesh2 is None:
            d, p = self.options.mesh_shape
            devs = jax.devices()
            if d * p > len(devs):
                raise ValueError(
                    f"mesh_shape {d}x{p} needs {d * p} devices, "
                    f"{len(devs)} available")
            self._mesh2 = Mesh(np.array(devs[: d * p]).reshape(d, p),
                               ("data", "pod"))
        return self._mesh2

    # -- incremental tile-prune mask cache (DESIGN.md §11) ------------------

    @trace.spanned("engine.mask_delta")
    def apply_mask_delta(self, delta):
        """Propagate a commit/retract ``MutationDelta`` into the mask cache.

        Called by the serving layer right after ``commit_rows`` /
        ``retract_rows`` so the next ``detect(..., index=...)`` reuses the
        cached block incidence (updated in O(touched cells)) instead of
        regathering all K chunk reductions. Returns an opaque undo token
        for commits — pair it with ``undo_mask_delta`` around a transient
        commit→detect→rollback — and None otherwise. Safe no-op when no
        cache exists yet; a delta that doesn't chain (wrong ``from_mseq``,
        compaction) just marks the cache stale for a fresh rebuild.
        """
        cache = self._mask_cache
        if cache is None or delta is None:
            return None
        inner = cache.apply(delta)
        return None if inner is None else (cache, inner)

    @trace.spanned("engine.mask_delta")
    def undo_mask_delta(self, token) -> None:
        """Reverse ``apply_mask_delta`` after the index store rolled back.

        Re-adopts the cache object the token came from (a detect between
        apply and undo may have swapped ``_mask_cache``), so the restored
        incidence — bit-exact to the pre-commit state — serves the next
        pass. ``None`` tokens are no-ops.
        """
        if token is None:
            return
        cache, inner = token
        cache.undo(inner)
        self._mask_cache = cache

    @trace.spanned("engine.mask_delta")
    def rebase_mask_cache(self, delta) -> None:
        """Re-anchor a cache adopted DURING a transient commit onto the base.

        The serving layer calls this (instead of ``undo_mask_delta``) when
        ``apply_mask_delta`` returned no token — i.e. no cache existed
        before the transient commit, so whatever the detect pass adopted is
        anchored on the mid-transient store state and would die with the
        rollback. ``BlockOrCache.rebase`` shrinks it back onto the restored
        base store so the NEXT batch chains off it incrementally.
        """
        cache = self._mask_cache
        if cache is None:
            return
        if delta is None:
            self.invalidate_mask_cache()
            return
        cache.rebase(delta)
        if cache.stale:
            self._mask_cache = None

    def invalidate_mask_cache(self) -> None:
        """Drop the mask cache (the next indexed detect rebuilds it fresh)."""
        if self._mask_cache is not None:
            self._mask_cache.stale = True
        self._mask_cache = None

    # -- dispatch -----------------------------------------------------------

    @trace.spanned("engine.detect")
    def detect(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        index: InvertedIndex | None = None,
        items: np.ndarray | None = None,
    ) -> DetectionResult:
        """Run one detection pass in this engine's mode (DESIGN.md §3).

        Args:
          ds: the (S, D) claims dataset.
          p_claim: (S, D) float32 — truth probability of the value each
            source provides per item (equal across providers of one value;
            ignored where values[s, d] < 0).
          index: a prebuilt ``InvertedIndex`` to reuse (modes that index);
            None → built here.
          items: sampled/sample_verify only — an explicit item-column subset
            overriding the configured sampler.

        Returns a ``DetectionResult`` over every ordered source pair;
        per-run diagnostics land in ``self.last_stats``.
        """
        trace.annotate(mode=self.mode)
        opt = self.options
        if self.mode == "pairwise":
            return pairwise_detect(ds, p_claim, self.cfg)
        if index is None and self.mode in ("exact", "bound", "bound+", "hybrid"):
            index = self._build_index(ds, p_claim)
        if self.mode == "exact":
            return index_detect_exact(ds, p_claim, self.cfg, index=index)
        if self.mode in ("bound", "bound+", "hybrid"):
            l_thr = opt.l_threshold
            if l_thr is None:
                l_thr = 16 if self.mode == "hybrid" else 0
            return bound_detect(
                ds, p_claim, self.cfg, n_buckets=opt.n_buckets,
                use_timers=self.mode in ("bound+", "hybrid"),
                l_threshold=l_thr, rescore_margin=opt.rescore_margin,
                index=index)
        if self.mode == "incremental":
            if self._inc_state is None:
                if index is None and opt.n_shards and opt.n_shards > 1:
                    index = self._build_index(ds, p_claim)
                result, self._inc_state = make_incremental_state(
                    ds, p_claim, self.cfg, n_buckets=opt.n_buckets,
                    chunk_entries=opt.store_chunk_entries,
                    chunk_bytes=opt.store_chunk_bytes, index=index)
                return result
            return incremental_detect(ds, p_claim, self.cfg, self._inc_state,
                                      rho=opt.rho, rho_acc=opt.rho_acc)
        if self.mode == "sampled":
            if items is None:
                items = self._sample_items(ds)
            sub = ds.subset_items(items)
            return self._detect_tiled(sub, p_claim[:, items])
        if self.mode == "sample_verify":
            return self._detect_sample_verify(ds, p_claim, items=items)
        return self._detect_tiled(ds, p_claim, index=index)

    def _sample_items(self, ds: ClaimsDataset) -> np.ndarray:
        opt = self.options
        if opt.sample_strategy == "item":
            return sample_by_item(ds, opt.sample_rate, seed=opt.sample_seed)
        if opt.sample_strategy == "cell":
            return sample_by_cell(ds, opt.sample_rate, seed=opt.sample_seed)
        return scale_sample(ds, opt.sample_rate,
                            min_per_source=opt.min_per_source,
                            seed=opt.sample_seed)

    # -- sample-then-verify (§VI sampling + exact candidate rescore) --------

    def _detect_sample_verify(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        items: np.ndarray | None = None,
    ) -> DetectionResult:
        """SCALESAMPLE for candidate-pair discovery, exact rescore to decide.

        DESIGN.md §4: the sampled tiled pass is only a *net* — every pair
        whose sampled decision margin lands within the recall slack of the
        copying boundary becomes a candidate, the slack widening until the
        shell of near-miss pairs thins below ``verify_miss_frac`` (the
        empirical bound on pairs the net might still miss). Candidates are
        then rescored exactly on the FULL dataset with the gathered dense
        rescore op (``rescore_pairs_exact``), so the final decision of every
        candidate pair provably equals ``index_detect_exact`` — sampling
        error survives only as recall loss of the net, never as a wrong
        decision on a discovered pair.
        """
        t0 = time.perf_counter()
        if items is None:
            items = self._sample_items(ds)

        # -- 1. cheap discovery: the tiled path on the sampled columns ------
        sub = ds.subset_items(items)
        sampled = self._detect_tiled(sub, p_claim[:, items])
        return self._sample_verify_finalize(
            ds, p_claim, items, sampled, self.last_stats,
            self._last_considered, t0)

    def _sample_verify_finalize(self, ds, p_claim, items, sampled,
                                sampled_stats, considered_s, t0):
        """Steps 2+3 of sample_verify: slack sweep + exact candidate rescore.

        Split from ``_detect_sample_verify`` so the shard-owner fan-out can
        run the sampled discovery pass as per-owner partials and still
        finish with the identical verification sweep
        (``finalize_owner_partials``).
        """
        cfg = self.cfg
        opt = self.options
        S = ds.n_sources

        # -- 2. recall-slack sweep: widen the candidate net -----------------
        # z < 0 ⇔ independent; sampling noise can push a true copying pair
        # below 0, so candidates are all pairs with z ≥ -slack. The sweep
        # widens slack geometrically until the next shell (-g·slack, -slack]
        # is nearly empty relative to the net — once the margin distribution
        # has a gap there, further widening buys ~no recall but rescores
        # strictly more pairs.
        z = (np.log(cfg.alpha / cfg.beta)
             + np.logaddexp(sampled.c_fwd, sampled.c_fwd.T))
        tri = np.triu(np.ones((S, S), bool), 1) & considered_s
        slack = float(opt.verify_slack)
        growth = max(float(opt.verify_slack_growth), 1.0 + 1e-6)
        z_floor = float(z[tri].min()) if tri.any() else 0.0
        sweep_rounds = 1
        while True:
            cand = tri & (z >= -slack)
            shell = tri & (z >= -slack * growth) & (z < -slack)
            n_cand, n_shell = int(cand.sum()), int(shell.sum())
            if (n_shell <= opt.verify_miss_frac * max(n_cand, 1)
                    or -slack <= z_floor):
                break
            slack *= growth
            sweep_rounds += 1

        # -- 3. exact gathered rescore of only the candidate pairs ----------
        pi, pj = np.nonzero(cand)
        c_fwd = np.zeros((S, S), np.float32)
        rescore_pairs_exact(ds, p_claim, cfg, pi, pj, c_fwd)
        considered = np.zeros((S, S), bool)
        considered[pi, pj] = considered[pj, pi] = True

        copying = decide_copying_np(c_fwd, c_fwd.T, cfg) & considered
        pr_ind = np.where(considered,
                          posterior_independence_np(c_fwd, c_fwd.T, cfg),
                          1.0).astype(np.float32)
        np.fill_diagonal(pr_ind, 1.0)
        np.fill_diagonal(copying, False)
        self._last_considered = considered     # == the candidate set

        prov = ds.provided_mask
        values_exact = (int(np.count_nonzero(prov[pi] & prov[pj]))
                        if len(pi) else 0)
        counter = ComputeCounter(
            pairs_considered=n_cand,
            shared_values_examined=(
                sampled.counter.shared_values_examined + values_exact),
            score_computations=(
                sampled.counter.score_computations + 2 * values_exact),
            index_entries=sampled.counter.index_entries,
        )
        self.last_stats = {
            "items_sampled": int(len(items)),
            "item_rate": round(len(items) / max(ds.n_items, 1), 4),
            "slack_final": round(slack, 3),
            "sweep_rounds": sweep_rounds,
            "candidate_pairs": n_cand,
            "shell_pairs": n_shell,
            "sampled_copying_pairs": len(sampled.copying_pairs()),
            "sampled_stats": sampled_stats,
        }
        return DetectionResult(c_fwd=c_fwd, pr_independent=pr_ind,
                               copying=copying, counter=counter,
                               wall_time_s=time.perf_counter() - t0)

    # -- the tiled + sharded production path --------------------------------

    def _build_index(self, ds: ClaimsDataset, p_claim: np.ndarray,
                     streaming: bool = False) -> InvertedIndex:
        """Build an index honoring this engine's store-chunking options.

        With ``n_shards`` set, the index's store is wrapped in a
        ``ShardedCorpusStore`` under a balanced row-range plan — every
        consumer (exact, bound, tiled, incremental) then reads rows through
        the shard facade, and the tiled path scans shard by shard.

        ``streaming=True`` (the one-shot tiled path) additionally streams
        the seal through the wrap when pack/spill options are set: blocks
        bitpack and spill under the LRU cap AS they are sliced, and source
        chunks release behind the slicing, so no host's peak-resident bytes
        exceed its slice budget even DURING the build (DESIGN.md §12). The
        mutating consumers (services, incremental state) keep the dense
        wrap — a sealed store refuses commits.
        """
        opt = self.options
        idx = build_index(ds, p_claim, self.cfg,
                          chunk_entries=opt.store_chunk_entries,
                          chunk_bytes=opt.store_chunk_bytes)
        if opt.n_shards and opt.n_shards > 1:
            plan = make_shard_plan(idx.store.n_rows, opt.n_shards)
            if streaming and (opt.shard_pack
                              or opt.shard_spill_bytes is not None):
                idx.store = shard_store(
                    idx.store, plan, pack=opt.shard_pack,
                    spill_dir=opt.shard_spill_dir,
                    resident_bytes=opt.shard_spill_bytes, consume=True)
            else:
                idx.store = shard_store(idx.store, plan)
        return idx

    def _tile_edge(self, s_sources: int) -> int:
        """Tile edge: the smallest multiple of 8 (f32 sublane alignment) that
        is ≥ min(S, requested tile) — tiny datasets pad by at most 7 sources
        instead of being blown up to a fixed 64-wide tile."""
        t = min(self.options.tile, max(1, s_sources))
        return max(8, -(-t // 8) * 8)

    # Inflation + slack constants live in scoring.bucket_score_deltas now
    # (shared with BOUND's error-aware freezes); kept as class attributes for
    # back-compat with callers that tuned them per engine.
    DELTA_INFLATION = 1.5
    DELTA_SLACK = 2e-3

    def _bucket_deltas(self, p_hat, p_lo, p_hi, acc: np.ndarray) -> np.ndarray:
        """Per-chunk bound δ_k ≳ |f(A_i, A_j, p) − f(A_i, A_j, p̂_k)| for any
        entry p in chunk k (``scoring.bucket_score_deltas``). Together with
        ``rescore_margin`` this makes the tiled decisions provably equal the
        exact INDEX — and the scaling benchmark cross-checks decision
        equality on every run."""
        return bucket_score_deltas(p_hat, p_lo, p_hi, acc, self.cfg,
                                   inflation=self.DELTA_INFLATION,
                                   slack=self.DELTA_SLACK)

    def _tile_kernel(self, v_dev, acc_vec, p_g, coords_g, T, d_g, o_g,
                     block):
        """One group pass: 1-D tile mesh, or data×pod when mesh_shape is set."""
        opt = self.options
        if opt.mesh_shape is not None:
            return sharded_tile_scores_2d(
                self.mesh2(), v_dev, acc_vec, p_g, coords_g, self.cfg,
                tile=T, delta=d_g, nout=o_g, impl=opt.kernel_impl,
                block_i=block, block_j=block)
        return sharded_tile_scores(
            self.mesh(), v_dev, acc_vec, p_g, coords_g, self.cfg, tile=T,
            delta=d_g, nout=o_g, impl=opt.kernel_impl,
            block_i=block, block_j=block)

    def _stage_v(self, v_np, dtype):
        """Host→device transfer of one group's v-slab, replicated over every
        device of the 1-D tile mesh (not only the default device).

        Runs on the prefetch thread when ``prefetch_depth`` ≥ 1, so the
        transfer of group G+1 hides behind group G's kernel. The 2-D
        (``mesh_shape``) path pod-pads the chunk axis host-side inside
        ``sharded_tile_scores_2d``, which then places the pod shards — v
        stays host-resident here and only the host assembly is overlapped.
        """
        v = np.asarray(v_np, np.dtype(dtype))
        if self.options.mesh_shape is not None:
            return v
        sharding = NamedSharding(self.mesh(), P())
        trace.annotate(bytes=v.nbytes)
        trace.count("engine.h2d_bytes", v.nbytes * len(sharding.device_set))
        return jax.device_put(v, sharding)

    def _gathers_on_device(self, store, S_pad: int) -> bool:
        """Whether the scan gathers its slabs on the device (DESIGN.md §6):
        on the 1-D mesh over an unsharded store, wherever the resident base
        incidence and the slabs in flight fit every device's memory (a
        store split over row shards never holds every row on one device).
        """
        opt = self.options
        if opt.mesh_shape is not None or isinstance(store,
                                                    ShardedCorpusStore):
            return False
        need = (devchunks.device_nbytes(store, S_pad)
                + (opt.prefetch_depth + 2) * opt.chunk_group_bytes)
        return devchunks.fits(self.mesh().devices.flat, need)

    # scatter lives in shardplan (shared with OwnerPartial.to_grids); the
    # staticmethod survives for callers that patched/tuned it per engine
    _scatter_tiles = staticmethod(scatter_tile_stacks)

    def _scan_shards(self, ech, coords, chunk_keep, acc_pad, T, n_blocks,
                     Gc, delta, block, dtype):
        """Per-shard tile scans over compact row-block slabs (DESIGN.md §10).

        Each shard owns the tiles whose ROW block falls inside its row
        range and assembles only the row blocks its tiles touch (row AND
        column sides) — never the full S_pad incidence. Per-tile kernel
        operands are identical to the unsharded scan, so per-tile outputs
        are bit-identical; tile placement across shards is disjoint, so
        the merge is exact. A shard failing mid-scan surfaces as ONE
        ``ShardScanError`` before any merge happens — no partial decision
        grids escape to the caller.
        """
        store = ech.store
        plan = store.plan
        S_pad = n_blocks * T
        last_row = max(plan.n_rows - 1, 0)
        owner = np.array([plan.owner_of_row(min(r * T, last_row))
                          for r in range(n_blocks)], np.int64)
        tile_keep = chunk_keep[:, coords[:, 0], coords[:, 1]]
        partials = []
        run_total = 0
        for s in range(store.n_shards):
            grids = [np.zeros((S_pad, S_pad), np.float32) for _ in range(4)]
            mine = owner[coords[:, 0]] == s
            if mine.any():
                try:
                    stacks, run = self._scan_one_shard(
                        ech, coords[mine], tile_keep[:, mine], acc_pad, T,
                        n_blocks, Gc, delta, block, dtype)
                except Exception as e:
                    # surface the ROOT fault as the cause: a staging
                    # failure arrives wrapped in PipelineStageError, but
                    # callers triage on the underlying I/O error
                    root = e.__cause__ if isinstance(
                        e, PipelineStageError) and e.__cause__ else e
                    raise ShardScanError(
                        s, f"tile scan failed: "
                           f"{type(e).__name__}: {e}") from root
                run_total += run
                if stacks is not None:
                    self._scatter_tiles(grids, coords[mine], stacks,
                                        n_blocks, T)
            partials.append(tuple(grids))
        return partials, run_total

    def _scan_one_shard(self, ech, coords_s, tile_keep_s, acc_pad, T,
                        n_blocks, Gc, delta, block, dtype):
        """Stream chunk groups for ONE shard's tiles over its compact slab.

        Group descriptors are enumerated up front on the caller's thread;
        slab assembly (the shard reads) + device staging run on the
        prefetcher's stage thread, ``prefetch_depth`` groups ahead of the
        kernel. Returns ``(stacks, chunk_tiles_run)`` — the five per-tile
        kernel channels as host float32 ``(len(coords_s), T, T)`` arrays
        (None when every group was pruned), which is exactly the
        ``OwnerPartial`` transport payload of the shard-owner fan-out.
        """
        store = ech.store
        K = ech.n_chunks
        b = ech.width
        blocks_needed = np.unique(coords_s)
        pos = np.full(n_blocks, -1, np.int64)
        pos[blocks_needed] = np.arange(len(blocks_needed))
        slab_rows = len(blocks_needed) * T
        coords_c = pos[coords_s].astype(np.int32)
        acc_slab = np.ascontiguousarray(
            acc_pad.reshape(n_blocks, T)[blocks_needed]).reshape(slab_rows)
        stacks = None
        run = 0
        groups = []
        for g0 in range(0, K, Gc):
            ks = list(range(g0, min(g0 + Gc, K)))
            gmask = tile_keep_s[ks].any(axis=0)
            if not gmask.any():
                continue
            run += int(gmask.sum()) * len(ks)
            groups.append((ks, gmask))

        def _stage(desc):
            ks, gmask = desc
            coords_g = np.where(gmask[:, None], coords_c, -1).astype(np.int32)
            p_g = np.full(Gc, 0.5, np.float32)
            d_g = np.zeros(Gc, np.float32)
            o_g = np.zeros(Gc, np.float32)
            v_np = np.zeros((slab_rows, Gc, b), np.int8)
            for i, k in enumerate(ks):
                for bi, blk in enumerate(blocks_needed):
                    v_np[bi * T:(bi + 1) * T, i, :] = store.assemble_rows(
                        int(k), int(blk) * T, (int(blk) + 1) * T)
                p_g[i] = ech.p_hat[k]
                d_g[i] = delta[k]
                o_g[i] = ech.nout[k]
            return self._stage_v(v_np, dtype), p_g, d_g, o_g, coords_g

        pf = ChunkPrefetcher(groups, _stage,
                             depth=self.options.prefetch_depth)
        try:
            for v_dev, p_g, d_g, o_g, coords_g in pf:
                with trace.span("engine.scan.dispatch"):
                    outs = self._tile_kernel(v_dev, acc_slab, p_g, coords_g,
                                             T, d_g, o_g, block)
                    stacks = (list(outs) if stacks is None
                              else [st + o for st, o in zip(stacks, outs)])
        finally:
            pf.close()
            for key in self._pipe:
                self._pipe[key] += getattr(pf, key)
        if stacks is not None:
            with trace.span("engine.scan.collect"):
                stacks = [np.asarray(s, np.float32)[: len(coords_s)]
                          for s in stacks]
        return stacks, run

    def _detect_tiled(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        index: InvertedIndex | None = None,
    ) -> DetectionResult:
        ctx = self._tiled_prologue(ds, p_claim, index)
        grids, chunk_tiles_run = self._run_tiled_scan(ctx)
        return self._tiled_finalize(ctx, grids, chunk_tiles_run)

    @trace.spanned("engine.prologue")
    def _tiled_prologue(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        index: InvertedIndex | None = None,
    ) -> TileScanContext:
        """Steps 1–2 of the tiled pass: index, chunking, pruning, sizing."""
        t0 = time.perf_counter()
        opt = self.options
        S = ds.n_sources
        T = self._tile_edge(S)
        n_blocks = -(-S // T)
        S_pad = n_blocks * T
        self._pipe = {"stage_wait_s": 0.0, "compute_wait_s": 0.0,
                      "staging_s": 0.0}
        base_idx = (index if index is not None
                    else self._build_index(ds, p_claim, streaming=True))
        # Incidence element type, resolved first: the chunk width depends on
        # its itemsize. 0/1 incidence makes int8 (the default) lossless —
        # the kernel accumulates it exactly in int32 on the MXU at half the
        # HBM traffic of bf16; bf16/f32 remain selectable for the
        # microbenchmark.
        dtypes = {"auto": jnp.int8, "int8": jnp.int8, "bf16": jnp.bfloat16,
                  "f32": jnp.float32}
        if opt.incidence_dtype not in dtypes:
            raise ValueError(
                f"unknown incidence_dtype {opt.incidence_dtype!r}; "
                f"expected one of {sorted(dtypes)}")
        dtype = dtypes[opt.incidence_dtype]
        itemsize = np.dtype(np.int8 if dtype == jnp.int8 else
                            np.float32 if dtype == jnp.float32
                            else np.float16).itemsize
        # p-ordered, region-padded, uniform-width chunks. The byte budget
        # caps the chunk width so even ONE shipped chunk respects it
        # (floored at 8 entries inside engine_order). On the unsharded 1-D
        # mesh the base incidence ships as it sits and every scan group
        # gathers its slab on the device (DESIGN.md §6); a row-sharded
        # store and the 2-D mesh scan host chunks, gathered here with rows
        # padded to the tile grid so they slice straight into pair tiles.
        base_store = base_idx.store
        sharded = isinstance(base_store, ShardedCorpusStore)
        resident = None
        with trace.span("engine.chunk_gather") as sp:
            ech = engine_order(
                base_idx, opt.n_buckets,
                max_width=opt.chunk_group_bytes // max(S_pad * itemsize, 1))
            if ech.n_chunks and self._gathers_on_device(base_store, S_pad):
                sharding = NamedSharding(self.mesh(), P())
                resident, nbytes = devchunks.upload(base_store, S_pad,
                                                    sharding)
                trace.count("engine.h2d_bytes",
                            nbytes * len(sharding.device_set))
            else:
                ech.gather(base_idx, row_capacity=S_pad)
            gather = "host" if resident is None else "device"
            sp.set(gather=gather)
        K = ech.n_chunks
        b = ech.width
        with trace.span("engine.bucket_deltas"):
            delta = self._bucket_deltas(ech.p_hat, ech.p_lo, ech.p_hi,
                                        ds.accuracy)
        # row-range sharded plane (DESIGN.md §10): the engine store is a
        # ShardedCorpusStore whenever the index's store was (gather_entries
        # preserves the plan). Sealing freezes it for the scan — optionally
        # bitpacked to 1 bit/entry and/or under a per-shard LRU byte cap
        # with cold blocks spilled to checksummed frames.
        if sharded and (opt.shard_pack or opt.shard_spill_bytes is not None):
            ech.store.seal(pack=opt.shard_pack,
                           spill_dir=opt.shard_spill_dir,
                           resident_bytes=opt.shard_spill_bytes)

        # ---- tile ∘ chunk pruning on the OR-reduced incidence -------------
        # Per chunk k, G_k[r] ORs the chunk's incidence over tile r's rows;
        # chunk_keep[k][r, c] ⇔ some row-block-r source shares some entry of
        # chunk k with some col-block-c source (an upper bound on any member
        # pair's co-occurrence, so both prunes are exact). A tile survives
        # if any NON-Ē chunk keeps it (the Ē suffix bound — pairs that
        # co-occur only inside Ē can never flip to copying); a surviving
        # tile then skips every chunk whose chunk_keep bit is off (its
        # contribution to all five channels would be zero). The keep matrix
        # is symmetric, so only unordered (r ≤ c) tiles are scheduled.
        cache = self._mask_cache if index is not None else None
        mask_source = "fresh"
        with trace.span("engine.tile_masks"):
            keep = np.zeros((n_blocks, n_blocks), bool)
            chunk_keep = np.zeros((K, n_blocks, n_blocks), bool)
            if (cache is not None and cache.matches(base_store, T)
                    and cache.block_inc.shape == (n_blocks,
                                                  base_store.n_entries)):
                # delta-maintained cache hit (DESIGN.md §11)
                mask_source = "cache"
                self._mask_cache_hits += 1
            elif ech.store is None:
                # no host chunks (device gather): reduce the BASE chunks;
                # adopted as the mask cache when detecting against a
                # persistent index
                cache = tilecache.BlockOrCache.build(base_store, T)
                if index is not None:
                    self._mask_cache = cache
                    self._mask_full_builds += 1
            else:
                cache = None
            if cache is not None:
                # each GATHERED chunk's mask permutes base columns through
                # the gather order — bit-equal to a fresh reduction of the
                # gathered chunk
                for k in range(K):
                    g_k = cache.chunk_mask(
                        ech.order[k * b:(k + 1) * b]).astype(np.int32)
                    chunk_keep[k] = (g_k @ g_k.T) > 0
                    if k < ech.ebar_chunk:
                        keep |= chunk_keep[k]
            else:
                # fresh full reduction of the host chunks (sharded stores
                # reduce shard by shard — no host assembles the full chunk).
                # When detecting against a persistent index, adopt the
                # result as the new mask cache at zero extra reduction
                # cost: scatter each gathered chunk's columns back to base
                # entry order.
                base_inc = None
                base_mseq = -1
                if index is not None:
                    base_inc = np.zeros((n_blocks, base_store.n_entries), bool)
                    base_mseq = getattr(base_store, "mseq", -1)
                for k in range(K):
                    g_bool = tilecache.chunk_block_inc(ech.store, k, T, n_blocks)
                    if base_inc is not None:
                        sel = ech.order[k * b: k * b + g_bool.shape[1]]
                        live = sel >= 0
                        if live.any():
                            base_inc[:, sel[live]] = g_bool[:, live]
                    g_k = g_bool.astype(np.int32)
                    chunk_keep[k] = (g_k @ g_k.T) > 0
                    if k < ech.ebar_chunk:
                        keep |= chunk_keep[k]
                if base_inc is not None:
                    self._mask_cache = tilecache.BlockOrCache(
                        base_store, T, base_mseq, base_inc)
                    self._mask_full_builds += 1
        coords = np.argwhere(np.triu(keep)).astype(np.int32)  # r ≤ c tiles
        tiles_total = n_blocks * (n_blocks + 1) // 2
        n_tiles = len(coords)

        # ---- stream chunk groups over the 1-D mesh ------------------------
        acc_pad = np.pad(ds.accuracy.astype(np.float32), (0, S_pad - S),
                         constant_values=0.5)

        block = 128 if T % 128 == 0 else T
        chunk_nbytes = S_pad * b * itemsize   # shipped (unpacked) slab bytes
        # the byte budget clamps every group (floored at one chunk) against
        # TRUE resident bytes: a sealed bitpacked shard plane holds 1
        # bit/entry, so packed stores stream 8× larger groups under the
        # same budget (each group's shipped slab is still unpacked per
        # assembly — peak_group_bytes reports that separately)
        if sharded and opt.shard_pack and ech.store.sealed:
            resident_nbytes = S_pad * (-(-b // 8))
        else:
            resident_nbytes = chunk_nbytes
        budget_chunks = max(
            1, opt.chunk_group_bytes // max(resident_nbytes, 1))
        if opt.chunk_group is not None:
            Gc = min(max(1, int(opt.chunk_group)), budget_chunks)
        else:
            # auto: fill the byte budget, but never ship ALL chunks in one
            # pass when the store is chunked — the full incidence is never
            # resident in a single allocation
            Gc = min(budget_chunks, max(1, K - 1))
        trace.annotate(chunks=K, width=b, mask_source=mask_source)
        return TileScanContext(
            t0=t0, ds=ds, p_claim=p_claim, base_idx=base_idx, ech=ech,
            delta=delta, sharded=sharded, S=S, T=T, n_blocks=n_blocks,
            S_pad=S_pad, acc_pad=acc_pad, block=block, dtype=dtype,
            chunk_keep=chunk_keep, coords=coords, tiles_total=tiles_total,
            n_tiles=n_tiles, Gc=Gc, chunk_nbytes=chunk_nbytes,
            resident_nbytes=resident_nbytes, mask_source=mask_source,
            gather=gather, resident=resident)

    @trace.spanned("engine.scan")
    def _run_tiled_scan(self, ctx: TileScanContext):
        """Step 3: the tile∘chunk scan — the four pair grids + run count."""
        opt = self.options
        ech, coords, delta = ctx.ech, ctx.coords, ctx.delta
        K, b = ech.n_chunks, ech.width
        T, n_blocks, S_pad, Gc = ctx.T, ctx.n_blocks, ctx.S_pad, ctx.Gc
        acc_pad, block, dtype = ctx.acc_pad, ctx.block, ctx.dtype
        n_tiles, chunk_keep = ctx.n_tiles, ctx.chunk_keep
        c_same = np.zeros((S_pad, S_pad), np.float32)
        n_cnt = np.zeros((S_pad, S_pad), np.float32)
        n_out = np.zeros((S_pad, S_pad), np.float32)
        err = np.zeros((S_pad, S_pad), np.float32)
        chunk_tiles_run = 0
        if n_tiles and K and ctx.sharded:
            # per-shard scans over compact row-block slabs; the merge takes
            # the MAX of the error channel (and the sum of the others —
            # placement is disjoint, so both are exact)
            partials, chunk_tiles_run = self._scan_shards(
                ech, coords, chunk_keep, acc_pad, T, n_blocks, Gc, delta,
                block, dtype)
            c_same, n_cnt, n_out, err = merge_shard_partials(
                partials, shape=(S_pad, S_pad))
        elif n_tiles and K:
            # per-tile accumulators live on device, KEEPING the mesh-padded
            # tile sharding (slicing mid-stream would reshard every group);
            # one host transfer at the end feeds the scatter. Peak resident
            # incidence = one group: S_pad · Gc · b elements.
            stacks = None
            tile_keep = chunk_keep[:, coords[:, 0], coords[:, 1]]  # (K, n_tiles)
            groups = []
            for g0 in range(0, K, Gc):
                ks = list(range(g0, min(g0 + Gc, K)))
                gmask = tile_keep[ks].any(axis=0)
                if not gmask.any():
                    continue
                # actual kernel work: a tile shipped with a group scans ALL
                # the group's chunks (the kernel can't skip single chunks),
                # so grouped streaming realizes less chunk pruning than the
                # per-chunk masks would allow — count what really runs
                chunk_tiles_run += int(gmask.sum()) * len(ks)
                groups.append((ks, gmask))
            trace.annotate(groups=len(groups))

            def _stage(desc):
                ks, gmask = desc
                # chunk-pruned tiles short-circuit via the (-1,-1) marker
                coords_g = np.where(gmask[:, None], coords,
                                    -1).astype(np.int32)
                p_g = np.full(Gc, 0.5, np.float32)
                d_g = np.zeros(Gc, np.float32)
                o_g = np.zeros(Gc, np.float32)
                p_g[: len(ks)] = ech.p_hat[ks]
                d_g[: len(ks)] = delta[ks]
                o_g[: len(ks)] = ech.nout[ks]
                if ctx.resident is not None:
                    # the group's columns, -1 (a zero column) past the end
                    cols = np.full(Gc * b, -1, np.int64)
                    seg = ech.order[ks[0] * b:(ks[-1] + 1) * b]
                    cols[: len(seg)] = seg
                    trace.count("engine.device_gathered_chunks", len(ks))
                    return (devchunks.gather(ctx.resident, cols, Gc, dtype),
                            p_g, d_g, o_g, coords_g)
                if Gc == 1:
                    # store chunks are already contiguous (S_pad, b) — ship
                    # a zero-copy view instead of re-copying the incidence
                    v_np = ech.store.chunks[ks[0]].reshape(S_pad, 1, b)
                else:
                    v_np = np.zeros((S_pad, Gc, b), np.int8)
                    for i, k in enumerate(ks):
                        v_np[:, i, :] = ech.store.chunks[k]
                return self._stage_v(v_np, dtype), p_g, d_g, o_g, coords_g

            pf = ChunkPrefetcher(groups, _stage, depth=opt.prefetch_depth)
            try:
                for v_dev, p_g, d_g, o_g, coords_g in pf:
                    # the kernel call; on an asynchronous backend only its
                    # dispatch, the device work landing in the collect
                    with trace.span("engine.scan.dispatch"):
                        outs = self._tile_kernel(v_dev, acc_pad, p_g,
                                                 coords_g, T, d_g, o_g, block)
                        stacks = (list(outs) if stacks is None
                                  else [s + o for s, o in zip(stacks, outs)])
            finally:
                pf.close()
                for key in self._pipe:
                    self._pipe[key] += getattr(pf, key)
            if stacks is None:
                stacks = [jnp.zeros((n_tiles, T, T), jnp.float32)] * 5
            # the device→host fetch of the tile stacks happens here
            with trace.span("engine.scan.collect"):
                self._scatter_tiles([c_same, n_cnt, n_out, err], coords,
                                    stacks, n_blocks, T)
        if ctx.resident is not None:
            # free the device copy; wait for its upload first, which may
            # still read host chunks that the caller changes after the pass
            ctx.resident.block_until_ready()
            ctx.resident = None
        return (c_same, n_cnt, n_out, err), chunk_tiles_run

    @trace.spanned("engine.finalize")
    def _tiled_finalize(self, ctx: TileScanContext, grids,
                        chunk_tiles_run: int) -> DetectionResult:
        """Step 4: INDEX step 3 + error-bounded exact rescore + decide."""
        cfg = self.cfg
        opt = self.options
        ds, p_claim = ctx.ds, ctx.p_claim
        ech, base_idx, S = ctx.ech, ctx.base_idx, ctx.S
        K, b = ech.n_chunks, ech.width
        T, Gc = ctx.T, ctx.Gc
        tiles_total, n_tiles = ctx.tiles_total, ctx.n_tiles
        dtype, sharded, mask_source = ctx.dtype, ctx.sharded, ctx.mask_source
        chunk_nbytes, resident_nbytes = ctx.chunk_nbytes, ctx.resident_nbytes
        t0 = ctx.t0
        c_same, n_cnt, n_out, err = grids
        # INDEX step 3 and the near-pair selection, then the rescore, then
        # posteriors, decisions and counts: ``engine.decide`` is all but
        # the rescore
        with trace.span("engine.decide"):
            c_same = c_same[:S, :S]
            n_cnt = n_cnt[:S, :S]
            err = err[:S, :S]
            considered = n_out[:S, :S] > 0.5
            np.fill_diagonal(considered, False)

            # ---- INDEX step 3 + error-bounded exact rescore ---------------
            c_fwd = np.where(
                considered, c_same + (base_idx.l_counts - n_cnt) * cfg.ln_1ms,
                0.0).astype(np.float32)
            np.fill_diagonal(c_fwd, 0.0)

            # a pair's decision can only differ from the exact INDEX if the
            # accumulated p̂ error reaches its decision margin — rescore
            # exactly every such pair (err bounds |Δ C→|; |Δz| ≤ max of both
            # directions)
            z = np.log(cfg.alpha / cfg.beta) + np.logaddexp(c_fwd, c_fwd.T)
            near = considered & (np.abs(z) <
                                 opt.rescore_margin + np.maximum(err, err.T))
            near &= np.triu(np.ones_like(near), 1).astype(bool)
            pi, pj = np.nonzero(near)
        n_rescored = rescore_pairs_exact(ds, p_claim, cfg, pi, pj, c_fwd)

        with trace.span("engine.decide"):
            pr_ind = posterior_independence_np(c_fwd, c_fwd.T, cfg)
            copying = decide_copying_np(c_fwd, c_fwd.T, cfg) & considered
            pr_ind = np.where(considered, pr_ind, 1.0).astype(np.float32)
            np.fill_diagonal(pr_ind, 1.0)
            np.fill_diagonal(copying, False)
            self._last_considered = considered

            # semantic (paper-metric) accounting, identical to the exact
            # INDEX
            iu = np.triu_indices(S, 1)
            values_examined = int(n_cnt[iu][considered[iu]].sum())
            n_pairs = int(considered[iu].sum())
            counter = ComputeCounter(
                pairs_considered=n_pairs,
                shared_values_examined=values_examined,
                score_computations=(2 * values_examined + 2 * n_pairs
                                    + 2 * n_rescored),
                index_entries=ech.n_live,
            )
        self.last_stats = {
            "tile": T,
            "tiles_total": tiles_total,        # unordered (r ≤ c) tiles
            "tiles_kept": n_tiles,
            "tiles_pruned": tiles_total - n_tiles,
            "schedule": "triangular",
            "incidence_dtype": str(np.dtype(dtype)),
            "n_devices": (int(np.prod(opt.mesh_shape)) if opt.mesh_shape
                          else self.mesh().shape["shards"]),
            "rescored_pairs": n_rescored,
            # chunked-store telemetry (DESIGN.md §6)
            "chunks": K,
            "chunk_width": b,
            "chunk_group": Gc,
            # chunk pairs over tiles that SURVIVED tile pruning — run/total
            # isolates the chunk-prune win (pre-tile-prune total = K·tiles_total)
            "chunk_tiles_total": K * n_tiles,
            "chunk_tiles_run": chunk_tiles_run,
            "peak_group_bytes": int(Gc * chunk_nbytes),
            "gather": ctx.gather,
            "resident_chunk_bytes": int(resident_nbytes),
            # async staging pipeline (DESIGN.md §11)
            "prefetch_depth": int(opt.prefetch_depth),
            "stage_wait_s": round(self._pipe["stage_wait_s"], 6),
            "compute_wait_s": round(self._pipe["compute_wait_s"], 6),
            "staging_s": round(self._pipe["staging_s"], 6),
            # incremental tile-prune mask cache (DESIGN.md §11)
            "mask_source": mask_source,
            "mask_cache_hits": self._mask_cache_hits,
            "mask_full_builds": self._mask_full_builds,
            "mask_blocks_updated": (self._mask_cache.blocks_updated
                                    if self._mask_cache is not None else 0),
        }
        if sharded:
            # shard-plane telemetry (DESIGN.md §10): what each host actually
            # held; the scaling bench asserts the peak against 1/shards of
            # the unsharded footprint
            self.last_stats.update({
                "n_shards": ech.store.n_shards,
                "shard_plan": ech.store.plan.sizes().tolist(),
                "shard_resident_bytes": ech.store.shard_resident_bytes(),
                "shard_peak_resident_bytes": ech.store.shard_peak_bytes(),
                "mesh_shape": (list(opt.mesh_shape) if opt.mesh_shape
                               else None),
            })
        return DetectionResult(c_fwd=c_fwd, pr_independent=pr_ind,
                               copying=copying, counter=counter,
                               wall_time_s=time.perf_counter() - t0)

    # -- shard-owner fan-out (DESIGN.md §12) --------------------------------

    #: engine modes the router fans out as per-owner partial tile scans;
    #: the remaining (host) modes read through the shard facade on one
    #: replica instead — both routes are bit-equal to single-host.
    OWNER_FANOUT_MODES = ("bucketed", "sampled", "sample_verify")

    def owner_scan_context(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        index: InvertedIndex | None = None,
    ) -> TileScanContext:
        """The shared fan-out prologue, computed once for all owners.

        Deterministic given (ds, p_claim, index, options): the router
        builds it on ONE engine and hands it to every owner's
        ``detect_owner_partial``, so index build, engine chunking, bucket
        deltas, and tile∘chunk pruning never rerun per owner. Sampled
        modes resolve their deterministic item subset here (the scan then
        runs over the subset views; ``items`` rides on the context for the
        sample_verify finalize). Requires a tiled fan-out mode and a
        row-range-sharded engine store.
        """
        if self.mode not in self.OWNER_FANOUT_MODES:
            raise ValueError(
                f"owner fan-out supports modes {self.OWNER_FANOUT_MODES}, "
                f"engine mode is {self.mode!r}")
        items = None
        if self.mode in ("sampled", "sample_verify"):
            items = self._sample_items(ds)
            sub = ds.subset_items(items)
            ctx = self._tiled_prologue(sub, p_claim[:, items])
        else:
            ctx = self._tiled_prologue(ds, p_claim, index)
        ctx.items = items
        if not ctx.sharded:
            raise ValueError(
                "owner fan-out requires a row-range-sharded engine store "
                "(build the index with n_shards > 1)")
        return ctx

    def detect_owner_partial(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        owner: int,
        index: InvertedIndex | None = None,
        ctx: TileScanContext | None = None,
    ) -> OwnerPartial:
        """ONE owner's share of the tiled pass (DESIGN.md §12).

        Scans only the surviving tiles whose ROW block falls in ``owner``'s
        row range — assembling just the row blocks those tiles touch, never
        the full incidence — and returns the per-tile kernel outputs as an
        ``OwnerPartial`` transport payload. Kernel operands are identical
        to the single-host scan, so per-tile outputs are bit-identical; a
        failure surfaces as one typed ``ShardScanError`` carrying the owner
        id (the router merges nothing for a failed wave).
        """
        if ctx is None:
            ctx = self.owner_scan_context(ds, p_claim, index=index)
        ech = ctx.ech
        store = ech.store
        owner = int(owner)
        if not 0 <= owner < store.n_shards:
            raise ValueError(
                f"owner {owner} out of range for {store.n_shards} owners")
        plan = store.plan
        T, n_blocks = ctx.T, ctx.n_blocks
        last_row = max(plan.n_rows - 1, 0)
        owners = np.array([plan.owner_of_row(min(r * T, last_row))
                           for r in range(n_blocks)], np.int64)
        mine = owners[ctx.coords[:, 0]] == owner
        coords_s = ctx.coords[mine]
        stacks = None
        run = 0
        if len(coords_s) and ech.n_chunks:
            tile_keep = ctx.chunk_keep[:, ctx.coords[:, 0], ctx.coords[:, 1]]
            try:
                stacks, run = self._scan_one_shard(
                    ech, coords_s, tile_keep[:, mine], ctx.acc_pad, T,
                    n_blocks, ctx.Gc, ctx.delta, ctx.block, ctx.dtype)
            except Exception as e:
                root = e.__cause__ if isinstance(
                    e, PipelineStageError) and e.__cause__ else e
                raise ShardScanError(
                    owner, f"owner tile scan failed: "
                           f"{type(e).__name__}: {e}") from root
        return OwnerPartial(owner=owner, n_blocks=n_blocks, tile=T,
                            coords=coords_s, stacks=stacks,
                            chunk_tiles_run=run)

    def finalize_owner_partials(
        self,
        ds: ClaimsDataset,
        p_claim: np.ndarray,
        ctx: TileScanContext,
        partials: list,
    ) -> DetectionResult:
        """Merge per-owner partials and finish the pass (router-side).

        Refuses to merge unless EVERY owner contributed exactly one partial
        — after an owner failure nothing merges, per the fault contract.
        Counts sum, the p̂-error bound maxes (``merge_owner_partials``), and
        the standard finalize (INDEX step 3, error-bounded exact rescore,
        decide) runs on the merged grids; for sample_verify the sampled
        merge then feeds the identical recall-slack sweep + exact candidate
        rescore over the FULL dataset. Decisions are bit-equal to the
        single-host engine by the §3.4 rescore argument.
        """
        store = ctx.ech.store
        got = sorted(int(p.owner) for p in partials)
        if got != list(range(store.n_shards)):
            raise ValueError(
                f"finalize_owner_partials: partials cover owners {got}, "
                f"need each of 0..{store.n_shards - 1} exactly once")
        grids = merge_owner_partials(list(partials), ctx.n_blocks, ctx.T)
        run = sum(int(p.chunk_tiles_run) for p in partials)
        result = self._tiled_finalize(ctx, grids, run)
        if self.mode == "sample_verify":
            return self._sample_verify_finalize(
                ds, p_claim, ctx.items, result, self.last_stats,
                self._last_considered, ctx.t0)
        return result


__all__ = ["DetectionEngine", "EngineOptions", "MODES", "TileScanContext"]
