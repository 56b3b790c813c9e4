"""Benchmark harness — one function per paper table/figure.

Every detection call routes through the ``DetectionEngine`` (core/engine.py),
the single entry point for all modes. Prints ``name,value,derived`` CSV rows
and, at the end of a run, writes a machine-readable ``BENCH_<run>.json`` so
CI and future PRs can diff the perf trajectory.

  table6  copy-detection + truth-finding quality vs PAIRWISE   (Table VI)
  table7  execution time + improvement cascade                 (Table VII)
  table8  INCREMENTAL/HYBRID per-round ratio + pass-1 %        (Table VIII)
  table9  sampling strategies                                  (Table IX)
  table10 time ratio vs FAGININPUT                             (Table X)
  fig2    single-round algorithms: computations + time         (Fig. 2)
  fig3    index orderings: BYCONTRIBUTION/BYPROVIDER/RANDOM    (Fig. 3)
  store   chunked CorpusStore: serve_batch host-copy bytes +   (store)
          req/s before/after the preallocated resident store;
          chunk-bytes-cap telemetry; decisions asserted equal
  mutate  live corpus mutation: commit_rows latency vs full    (mutation)
          re-index rebuild (≥5× asserted), commit+detect vs
          rebuild+detect under a skewed request mix (cache hit
          rate emitted), decisions asserted == rebuild
  durability  durable DetectionService: restore (snapshot +    (DESIGN §8)
          log-tail replay) vs rebuild-from-claims (≥5×
          asserted), raw replay rate in commits/s, restored
          decisions asserted == never-restarted service
  serve   batched serving: req/s + p50/p99 latency vs batch    (serving)
          size; asserts batched == per-request decisions and
          sample_verify == exact on its candidate set
  overload  traffic hardening: sustained req/s, shed rate and  (DESIGN §9)
          admitted-p99 under a 2× mixed commit/retract/read
          overload (deadline admission control + adaptive
          batching, p99 ≤ 1.5× unloaded asserted); commit
          circuit breaker trip/recovery with epoch equality;
          retraction asserted == rebuild-without-source
  scaling DetectionEngine matrix: S × device-count; with       (engine)
          --sharded adds the S=16384 row-range-sharded storage
          tier (bitpack + spill, per-shard peak-resident bytes
          asserted < 1/n_shards of the unsharded footprint)
  multihost shard-owner fleet (DESIGN §12): 4-owner router     (multi-host)
          decisions bit-equal to single-host + commit-routing
          latency; streaming-seal build of the row-range tier
          with max per-host peak-resident bytes asserted
          < 1/n_owners of unsharded DURING the build; --full
          adds the S=1,000,000 tier
  pipeline  async double-buffered chunk staging vs sync       (DESIGN §11)
          (decisions == exact asserted, stage-wait < sync
          staging time at S=2048), commit→detect zero
          full-chunk regathers + O(touched) mask-cell updates,
          (tile × chunk_group) autotune cached for `scaling`
  kernel  copyscore tile path: legacy two-orientation vs fused (engine)
          triangular dual-direction, f32/bf16 vs int8 incidence
  lm      token-throughput smoke of the training substrate

Run:  PYTHONPATH=src python -m benchmarks.run [table6 scaling ...]
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from benchmarks.datasets import BENCH_SPECS, SCALING_SPECS, SMALL, load, pairwise_mode
from repro.core import (
    CopyConfig,
    DetectionEngine,
    fagin_input,
    pair_f_measure,
    truth_finding,
)
from repro.core.index import InvertedIndex, build_index
from repro.core.truthfind import fusion_accuracy

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0)
ROWS = []
FLAGS = set()   # --flags stripped from argv by main(); tables may consult


def emit(name: str, value, derived=""):
    ROWS.append((name, value, derived))
    print(f"{name},{value},{derived}", flush=True)


def _engine(mode: str, **kw) -> DetectionEngine:
    return DetectionEngine(CFG, mode=mode, **kw)


def _pairwise_time(name, sc, p):
    """Full or 10%-extrapolated PAIRWISE wall time."""
    if pairwise_mode(name) == "full":
        res = _engine("pairwise").detect(sc.dataset, p)
        return res.wall_time_s, res
    D = sc.dataset.n_items
    sub_idx = np.arange(0, D, 10)
    sub = sc.dataset.subset_items(sub_idx)
    res = _engine("pairwise").detect(sub, p[:, sub_idx])
    return res.wall_time_s * (D / len(sub_idx)), None


# ---------------------------------------------------------------------------

def table6():
    """Copy-detection P/R/F + truth-finding agreement vs PAIRWISE."""
    for name in SMALL:
        sc, p = load(name)
        ref = _engine("pairwise").detect(sc.dataset, p)
        truth = ref.copying_pairs()
        ref_fusion = truth_finding(sc.dataset, CFG, detector="pairwise",
                                   max_rounds=5)

        methods = {
            "sample1": _engine("sampled", sample_strategy="item",
                               sample_rate=0.1, sample_seed=1),
            "index": _engine("bucketed"),
            "hybrid": _engine("hybrid"),
            "scalesample": _engine("sampled", sample_strategy="scale",
                                   sample_rate=0.1, min_per_source=4,
                                   sample_seed=1),
        }
        for m, eng in methods.items():
            res = eng.detect(sc.dataset, p)
            prec, rec, f = pair_f_measure(res.copying_pairs(), truth)
            emit(f"table6/{name}/{m}/precision", round(prec, 3))
            emit(f"table6/{name}/{m}/recall", round(rec, 3))
            emit(f"table6/{name}/{m}/f_measure", round(f, 3))
        # truth-finding agreement: accuracy variance vs pairwise fusion
        fus = truth_finding(sc.dataset, CFG, detector="hybrid", max_rounds=5)
        acc_var = float(np.abs(fus.accuracy - ref_fusion.accuracy).mean())
        fusion_acc = fusion_accuracy(fus, sc.dataset, sc.true_values)
        emit(f"table6/{name}/hybrid/accuracy_variance", round(acc_var, 4))
        emit(f"table6/{name}/hybrid/fusion_accuracy", round(fusion_acc, 3))


def table7():
    """Execution time cascade (PAIRWISE → … → SCALESAMPLE)."""
    for name in BENCH_SPECS:
        sc, p = load(name)
        t_pair, _ = _pairwise_time(name, sc, p)
        mode = pairwise_mode(name)
        emit(f"table7/{name}/pairwise/seconds", round(t_pair, 3),
             "extrapolated_from_10pct" if mode == "extrapolate" else "measured")

        t0 = time.perf_counter()
        _engine("sampled", sample_strategy="item", sample_rate=0.1,
                sample_seed=1).detect(sc.dataset, p)
        t_sample1 = time.perf_counter() - t0
        emit(f"table7/{name}/sample1/seconds", round(t_sample1, 3),
             f"improvement={1 - t_sample1 / t_pair:.1%}")

        res = _engine("bucketed").detect(sc.dataset, p)
        emit(f"table7/{name}/index/seconds", round(res.wall_time_s, 3),
             f"improvement={1 - res.wall_time_s / t_pair:.1%}")
        t_prev = res.wall_time_s

        res = _engine("hybrid").detect(sc.dataset, p)
        emit(f"table7/{name}/hybrid/seconds", round(res.wall_time_s, 3),
             f"improvement={1 - res.wall_time_s / max(t_prev, 1e-9):.1%}")
        t_prev = res.wall_time_s

        # incremental round (state built once = rounds 1–2 cost, then deltas)
        inc = _engine("incremental")
        inc.detect(sc.dataset, p)
        rng = np.random.default_rng(0)
        p2 = np.clip(p + np.where(p > 0, rng.normal(0, 0.005, p.shape), 0),
                     1e-3, 0.999).astype(np.float32)
        res = inc.detect(sc.dataset, p2)
        emit(f"table7/{name}/incremental/seconds", round(res.wall_time_s, 3),
             f"improvement={1 - res.wall_time_s / max(t_prev, 1e-9):.1%}")

        t0 = time.perf_counter()
        _engine("sampled", sample_strategy="scale", sample_rate=0.1,
                min_per_source=4, sample_seed=1).detect(sc.dataset, p)
        t_ss = time.perf_counter() - t0
        emit(f"table7/{name}/scalesample/seconds", round(t_ss, 3),
             f"total_improvement={1 - t_ss / t_pair:.2%}")


def table8():
    """INCREMENTAL vs HYBRID per round + pass-1 settlement."""
    for name in SMALL:
        sc, p = load(name)
        hyb = _engine("hybrid").detect(sc.dataset, p)
        inc = _engine("incremental")
        inc.detect(sc.dataset, p)
        rng = np.random.default_rng(1)
        pk = p
        for rnd in range(3, 6):
            pk = np.clip(pk + np.where(pk > 0, rng.normal(0, 0.004, pk.shape), 0),
                         1e-3, 0.999).astype(np.float32)
            res = inc.detect(sc.dataset, pk)
            ratio = res.wall_time_s / max(hyb.wall_time_s, 1e-9)
            emit(f"table8/{name}/round{rnd}/time_ratio", round(ratio, 4),
                 f"pass1_settled={inc.incremental_state.pass1_settled:.1%}")


def table9():
    """Sampling strategies at matched rates."""
    from repro.core import sample_by_cell, sample_by_item, scale_sample
    for name in SMALL:
        sc, p = load(name)
        ref = _engine("pairwise").detect(sc.dataset, p)
        truth = ref.copying_pairs()
        idx_ss = scale_sample(sc.dataset, 0.1, min_per_source=4, seed=1)
        rate_items = len(idx_ss) / sc.dataset.n_items
        cells = sc.dataset.provided_mask[:, idx_ss].sum() / sc.dataset.provided_mask.sum()
        strategies = {
            "scalesample": idx_ss,
            "byitem": sample_by_item(sc.dataset, rate_items, seed=1),
            "bycell": sample_by_cell(sc.dataset, cells, seed=1),
        }
        eng = _engine("sampled")
        for s_name, items in strategies.items():
            res = eng.detect(sc.dataset, p, items=items)
            prec, rec, f = pair_f_measure(res.copying_pairs(), truth)
            emit(f"table9/{name}/{s_name}/f_measure", round(f, 3),
                 f"prec={prec:.2f} rec={rec:.2f}")


def table10():
    """HYBRID / INCREMENTAL time as a ratio of FAGININPUT generation."""
    for name in SMALL:
        sc, p = load(name)
        idx = build_index(sc.dataset, p, CFG)
        *_, t_fagin = fagin_input(sc.dataset, p, CFG, index=idx)
        hyb = _engine("hybrid").detect(sc.dataset, p, index=idx)
        emit(f"table10/{name}/hybrid/ratio",
             round(hyb.wall_time_s / max(t_fagin, 1e-9), 3),
             f"fagin={t_fagin:.3f}s")
        inc = _engine("incremental")
        inc.detect(sc.dataset, p)
        rng = np.random.default_rng(2)
        p2 = np.clip(p + np.where(p > 0, rng.normal(0, 0.005, p.shape), 0),
                     1e-3, 0.999).astype(np.float32)
        res = inc.detect(sc.dataset, p2)
        emit(f"table10/{name}/incremental/ratio",
             round(res.wall_time_s / max(t_fagin, 1e-9), 3))


def fig2():
    """Single-round algorithms: computations and wall time."""
    for name in SMALL:
        sc, p = load(name)
        idx = build_index(sc.dataset, p, CFG)
        engines = {
            "index": _engine("bucketed"),
            "bound": _engine("bound"),
            "bound+": _engine("bound+"),
            "hybrid": _engine("hybrid"),
        }
        for a, eng in engines.items():
            eng.detect(sc.dataset, p, index=idx)      # warm-up (JIT compile)
            res = eng.detect(sc.dataset, p, index=idx)
            emit(f"fig2/{name}/{a}/computations", res.counter.total,
                 f"seconds={res.wall_time_s:.3f}")


def fig3():
    """Entry orderings: BYCONTRIBUTION (ours) vs BYPROVIDER vs RANDOM."""
    for name in SMALL:
        sc, p = load(name)
        base = build_index(sc.dataset, p, CFG)
        nprov = np.concatenate(
            [ch.V.sum(axis=0) for ch in base.store.iter_chunks()])
        orders = {
            "bycontribution": np.arange(base.n_entries),
            "byprovider": np.argsort(nprov, kind="stable"),
            "random": np.random.default_rng(0).permutation(base.n_entries),
        }
        eng = _engine("bound+")
        for o_name, order in orders.items():
            idx = InvertedIndex(
                store=base.store.gather_entries(order),
                ebar_start=base.n_entries if o_name != "bycontribution"
                else base.ebar_start,
                l_counts=base.l_counts,
                items_per_source=base.items_per_source,
            )
            eng.detect(sc.dataset, p, index=idx)
            res = eng.detect(sc.dataset, p, index=idx)
            emit(f"fig3/{name}/{o_name}/computations", res.counter.total,
                 f"seconds={res.wall_time_s:.3f}")


def scaling():
    """DetectionEngine scenario matrix: sources × device count.

    Single- vs multi-device (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise the
    sharded path on CPU); decisions are cross-checked against the exact
    INDEX where that reference is tractable.
    """
    import jax
    from repro.data.claims import oracle_claim_probs, synthetic_claims
    from repro.runtime.platform import load_autotune

    n_all = len(jax.devices())
    tuned = load_autotune()     # winner of `pipeline`'s sweep, if it ran
    if tuned is not None:
        emit("scaling/autotuned", 1,
             f"tile={tuned['tile']} chunk_group={tuned['chunk_group']} "
             f"backend={tuned['backend']}")
    for n_sources, spec in SCALING_SPECS.items():
        sc = synthetic_claims(spec)
        p = oracle_claim_probs(sc)
        idx = build_index(sc.dataset, p, CFG)
        exact = (_engine("exact").detect(sc.dataset, p, index=idx)
                 if n_sources <= 512 else None)
        kw = (dict(tile=tuned["tile"], chunk_group=tuned["chunk_group"])
              if tuned is not None
              else dict(tile=min(256, max(64, n_sources // 4))))
        for n_dev in sorted({1, n_all}):
            eng = _engine("bucketed", devices=n_dev, **kw)
            eng.detect(sc.dataset, p, index=idx)      # warm-up (JIT compile)
            res = eng.detect(sc.dataset, p, index=idx)
            st = eng.last_stats
            emit(f"scaling/S{n_sources}/dev{n_dev}/seconds",
                 round(res.wall_time_s, 3),
                 f"tile={st['tile']} tiles={st['tiles_kept']}/{st['tiles_total']}")
            emit(f"scaling/S{n_sources}/dev{n_dev}/pairs_considered",
                 res.counter.pairs_considered,
                 f"pruned_tiles={st['tiles_pruned']}")
            if exact is not None:
                match = bool(np.array_equal(res.copying, exact.copying))
                emit(f"scaling/S{n_sources}/dev{n_dev}/decisions_match_exact",
                     int(match))
    if "--sharded" in FLAGS:
        scaling_sharded()


def scaling_sharded():
    """Row-range-sharded storage plane at S where S² grids are off-limits.

    Builds a synthetic incidence store, shards it by row range
    (core/shardplan.py, DESIGN §10), seals each shard bitpacked
    (1 bit/entry) under an LRU spill budget, then sweeps every chunk
    through the assembly and pruning primitives the tiled scan uses.
    No host ever materializes more than its row slice: max per-shard
    peak-resident incidence bytes is asserted < 1/n_shards of the
    unsharded store's resident footprint, and sampled row windows are
    asserted bit-equal to the unsharded chunks (pack + spill lossless).
    CI runs ``benchmarks.run scaling --sharded`` and checks the
    ``shard_resident_ok`` row in BENCH_scaling.json.
    """
    import tempfile

    from repro.core import CorpusStore, shard_store

    sizes = [16384] + ([100_000] if "--full" in FLAGS else [])
    n_shards, chunk_entries, n_chunks = 4, 1024, 8
    T = 512
    for S in sizes:
        rng = np.random.default_rng(S)
        chunks = [(rng.random((S, chunk_entries)) < 0.02).astype(np.int8)
                  for _ in range(n_chunks)]
        E = chunk_entries * n_chunks
        base = CorpusStore(
            chunks=chunks,
            entry_item=np.arange(E, dtype=np.int32),
            entry_value=np.zeros(E, np.int32),
            entry_p=np.full(E, 0.5, np.float32),
            entry_score=np.zeros(E, np.float32),
            chunk_entries=chunk_entries, n_rows=S, capacity=S)
        unsharded = sum(c.nbytes for c in base.chunks)

        sh = shard_store(base, n_shards)
        with tempfile.TemporaryDirectory() as spill:
            # budget: half of each shard's bitpacked slice stays resident
            packed_slice = unsharded // 8 // n_shards
            sh.seal(pack=True, spill_dir=spill,
                    resident_bytes=max(1, packed_slice // 2))
            n_blocks = -(-S // T)
            # warm-up sweep faults the LRU to its detect-time working set,
            # THEN reset: the measured pass's peak reflects steady-state
            # detect residency, not the seal/build transients (ISSUE 10)
            for c in range(sh.n_chunks):
                sh.block_or(c, T, n_blocks)
                sh.assemble_rows(c, 0, min(T, S))
            sh.reset_peak_bytes()
            t0 = time.perf_counter()
            for c in range(sh.n_chunks):
                sh.block_or(c, T, n_blocks)           # tile∘chunk pruning
                for r0 in range(0, S, 4096):          # scan-slab assembly
                    sh.assemble_rows(c, r0, min(r0 + T, S))
            sweep_s = time.perf_counter() - t0
            # bit-exactness through pack + spill: sampled row windows
            for c, r0 in [(0, 0), (n_chunks - 1, S - T),
                          (n_chunks // 2, (S // 2) - 7)]:
                got = sh.assemble_rows(c, r0, r0 + T)
                assert np.array_equal(got, base.chunks[c][r0:r0 + T]), \
                    f"sharded assembly diverged at chunk {c} rows {r0}"
            peak = max(sh.shard_peak_bytes())
        bound = unsharded // n_shards
        ok = peak < bound
        emit(f"scaling/S{S}/shards{n_shards}/unsharded_resident_bytes",
             unsharded, f"chunks={n_chunks}x{chunk_entries} int8")
        emit(f"scaling/S{S}/shards{n_shards}/max_shard_peak_resident_bytes",
             peak, f"bound={bound} packed=1bit sweep_s={sweep_s:.2f}")
        emit(f"scaling/S{S}/shards{n_shards}/shard_resident_ok", int(ok))
        assert ok, (f"shard residency: peak {peak} >= {bound} "
                    f"(unsharded {unsharded} / {n_shards} shards)")


def multihost():
    """Multi-host shard-owner tier (ISSUE 10, DESIGN §12).

    Three legs. (1) S=512 owner-router equivalence: a 4-owner
    ``ReplicaRouter`` in shard-owner mode must reproduce single-host
    decisions bit-for-bit, and the owner-range commit routing latency is
    measured (``commit_route_ms``). (2) The streaming-build residency bar:
    a synthetic incidence store is sliced into owner shards THROUGH the
    streaming seal (``shard_store(pack, spill, resident_bytes,
    consume=True)``) — peaks are read with NO reset, so the asserted
    ``max_host_peak_resident_bytes < unsharded / n_owners`` bound covers
    the build itself, not just the detect pass. (3) The detect data plane
    (tile∘chunk ``block_or`` pruning + scan-slab ``assemble_rows``) is
    swept over every chunk and timed. Default tier S=16384 (CI smoke
    checks the ``host_resident_ok`` row in BENCH_multihost.json);
    ``--full`` adds the S=1,000,000 tier, built from scratch without any
    host ever holding more than one source chunk plus its capped shard
    residents (S² grids and the S×S ``l_counts`` of ``build_index`` are
    both off-limits at that scale, so the tier exercises the storage and
    scan primitives the tiled fan-out path runs on, not the full engine).
    """
    import tempfile

    from repro.core import CorpusStore, make_shard_plan, shard_store
    from repro.core.serving import DetectRequest, DetectionService, ReplicaRouter
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
        synthetic_query_rows,
    )

    owners = 4

    # ---- 1. owner-router equivalence + commit routing latency (S=512) -----
    sc = synthetic_claims(SyntheticSpec(
        n_sources=512, n_items=1536, coverage="book", n_cliques=14,
        clique_size=3, clique_items=12, seed=0))
    p = oracle_claim_probs(sc)
    vals, acc, pq, _ = synthetic_query_rows(sc, 24, seed=3)
    req = DetectRequest(rid=1, values=vals[:4], accuracy=acc[:4],
                        p_claim=pq[:4])

    def serve_one(svc):
        fut = svc.submit(req)
        svc.flush()
        return fut.result()

    single = DetectionService(sc.dataset, p, CFG, mode="bucketed", tile=64)
    router = ReplicaRouter(sc.dataset, p, CFG, shard_owners=owners,
                           mode="bucketed", tile=64)
    ref, got = serve_one(single), serve_one(router)
    match = (bool(np.array_equal(got.copying, ref.copying))
             and np.array_equal(got.c_fwd, ref.c_fwd))
    emit(f"multihost/S512/owners{owners}/decisions_match_single_host",
         int(match), f"fanout_wall={got.engine_wall_s:.3f}s")
    assert match, "shard-owner router decisions diverged from single-host"
    route_ms = []
    for k in range(4, 24, 4):                 # 5 routed commits of 4 rows
        t0 = time.perf_counter()
        router.commit(vals[k:k + 4], acc[k:k + 4], pq[k:k + 4])
        route_ms.append((time.perf_counter() - t0) * 1e3)
    plan = router._owner_plan()
    emit(f"multihost/S512/owners{owners}/commit_route_ms",
         round(float(np.median(route_ms)), 2),
         f"rows=4 tail_owner={plan.owner_of_row(plan.n_rows - 1)}")

    # ---- 2+3. streaming-build residency bar + detect-plane sweep ----------
    sizes = [16384] + ([1_000_000] if "--full" in FLAGS else [])
    for S in sizes:
        ce = 512
        n_chunks = 8 if S <= 16384 else 4
        T = 512
        rng = np.random.default_rng(S)
        chunks = []
        for _ in range(n_chunks):
            blk = np.empty((S, ce), np.int8)
            for r0 in range(0, S, 1 << 16):   # strip-wise: bounded temporaries
                r1 = min(r0 + (1 << 16), S)
                blk[r0:r1] = (rng.integers(0, 1000, (r1 - r0, ce),
                                           dtype=np.int16) < 20)
            chunks.append(blk)
        E = ce * n_chunks
        base = CorpusStore(
            chunks=chunks,
            entry_item=np.arange(E, dtype=np.int32),
            entry_value=np.zeros(E, np.int32),
            entry_p=np.full(E, 0.5, np.float32),
            entry_score=np.zeros(E, np.float32),
            chunk_entries=ce, n_rows=S, capacity=S)
        unsharded = sum(c.nbytes for c in base.chunks)
        # reference windows copied out BEFORE the consuming build
        probes = [(0, 0), (n_chunks - 1, S - T), (n_chunks // 2, (S // 2) - 7)]
        refs = {(c, r0): base.chunks[c][r0:r0 + T].copy() for c, r0 in probes}

        plan = make_shard_plan(S, owners)
        with tempfile.TemporaryDirectory() as spill:
            budget = max(1, unsharded // 8 // owners // 2)
            t0 = time.perf_counter()
            sh = shard_store(base, plan, pack=True, spill_dir=spill,
                             resident_bytes=budget, consume=True)
            build_s = time.perf_counter() - t0
            # NO reset_peak_bytes here: the bar covers the build itself
            peak = max(sh.shard_peak_bytes())
            bound = unsharded // owners
            ok = peak < bound
            n_blocks = -(-S // T)
            t0 = time.perf_counter()
            for c in range(sh.n_chunks):
                sh.block_or(c, T, n_blocks)           # tile∘chunk pruning
                for r0 in range(0, S, max(4096, S // 64)):
                    sh.assemble_rows(c, r0, min(r0 + T, S))
            sweep_s = time.perf_counter() - t0
            for (c, r0), want in refs.items():        # pack+spill lossless
                assert np.array_equal(sh.assemble_rows(c, r0, r0 + T), want), \
                    f"owner-shard assembly diverged at chunk {c} rows {r0}"
            peak_total = max(sh.shard_peak_bytes())
        emit(f"multihost/S{S}/owners{owners}/unsharded_resident_bytes",
             unsharded, f"chunks={n_chunks}x{ce} int8")
        emit(f"multihost/S{S}/owners{owners}/build_seconds",
             round(build_s, 3), "streaming seal: pack+spill DURING build")
        emit(f"multihost/S{S}/owners{owners}/max_host_peak_resident_bytes",
             peak_total, f"build_peak={peak} bound={bound} budget={budget}")
        emit(f"multihost/S{S}/owners{owners}/host_resident_ok",
             int(ok and peak_total < bound))
        emit(f"multihost/S{S}/owners{owners}/detect_plane_seconds",
             round(sweep_s, 3), f"tiles_T={T} chunks={n_chunks}")
        assert ok and peak_total < bound, (
            f"host residency: peak {max(peak, peak_total)} >= {bound} "
            f"(unsharded {unsharded} / {owners} owners)")


def pipeline():
    """Async chunk pipeline + delta-aware mask cache (DESIGN §11).

    Four legs: (1) decisions == exact INDEX with the prefetcher on;
    (2) S=2048 sync (prefetch_depth=0) vs double-buffered staging —
    prefetch wall must not regress and the consumer's stage-wait must
    undercut the synchronous path's total staging time; (3) commit→detect
    through a DetectionService does ZERO full-chunk regathers (counted by
    monkeypatching ``tilecache.chunk_block_inc``) and O(touched) mask-cell
    updates; (4) a small (tile × chunk_group) autotune sweep whose winner
    is cached for later ``scaling`` runs.
    """
    import jax
    from repro.core import tilecache
    from repro.core.serving import DetectRequest, DetectionService
    from repro.data.claims import (
        oracle_claim_probs,
        synthetic_claims,
        synthetic_query_rows,
    )
    from repro.runtime.platform import autotune

    n_dev = len(jax.devices())

    # ---- 1. bit-exactness with the prefetcher on (S=512) ------------------
    sc5 = synthetic_claims(SCALING_SPECS[512])
    p5 = oracle_claim_probs(sc5)
    idx5 = build_index(sc5.dataset, p5, CFG)
    exact = _engine("exact").detect(sc5.dataset, p5, index=idx5)
    for depth in (0, 2):
        eng = _engine("bucketed", tile=128, chunk_group=2,
                      prefetch_depth=depth)
        eng.detect(sc5.dataset, p5, index=idx5)       # warm-up (JIT compile)
        res = eng.detect(sc5.dataset, p5, index=idx5)
        match = bool(np.array_equal(res.copying, exact.copying))
        emit(f"pipeline/S512/dev{n_dev}/depth{depth}/decisions_match_exact",
             int(match), f"wall={res.wall_time_s:.3f}s")
        assert match, f"prefetch_depth={depth} diverged from exact"

    # ---- 2. S=2048: synchronous vs double-buffered staging ----------------
    sc = synthetic_claims(SCALING_SPECS[2048])
    p = oracle_claim_probs(sc)
    idx = build_index(sc.dataset, p, CFG)

    def best_of(depth, n=3):
        eng = _engine("bucketed", tile=256, chunk_group=2,
                      prefetch_depth=depth)
        eng.detect(sc.dataset, p, index=idx)          # warm-up (JIT compile)
        walls, stats = [], None
        for _ in range(n):
            r = eng.detect(sc.dataset, p, index=idx)
            walls.append(r.wall_time_s)
            if stats is None or r.wall_time_s == min(walls):
                stats = dict(eng.last_stats)
        return min(walls), stats

    wall_sync, st_sync = best_of(0)
    wall_pre, st_pre = best_of(2)
    emit(f"pipeline/S2048/dev{n_dev}/sync_seconds", round(wall_sync, 3),
         f"staging_s={st_sync['staging_s']} stage_wait_s="
         f"{st_sync['stage_wait_s']}")
    emit(f"pipeline/S2048/dev{n_dev}/prefetch_seconds", round(wall_pre, 3),
         f"staging_s={st_pre['staging_s']} stage_wait_s="
         f"{st_pre['stage_wait_s']} depth={st_pre['prefetch_depth']}")
    emit(f"pipeline/S2048/dev{n_dev}/prefetch_speedup",
         round(wall_sync / max(wall_pre, 1e-9), 3))
    # 5% slack absorbs scheduler jitter; the real overlap win is the
    # stage-wait assertion below (wait < the sync path's total staging)
    assert wall_pre <= wall_sync * 1.05, \
        f"prefetch regressed: {wall_pre:.3f}s vs sync {wall_sync:.3f}s"
    stall_ok = st_pre["stage_wait_s"] < st_sync["staging_s"]
    emit(f"pipeline/S2048/dev{n_dev}/stage_wait_lt_sync_staging",
         int(stall_ok),
         f"{st_pre['stage_wait_s']} < {st_sync['staging_s']}")
    assert stall_ok, (
        f"no staging overlap: prefetch stage_wait {st_pre['stage_wait_s']}s "
        f">= sync staging {st_sync['staging_s']}s")

    # ---- 3. commit→detect: zero regathers, O(touched) mask work -----------
    vals, acc, pq, _ = synthetic_query_rows(sc5, 8, seed=1)
    reqs = [DetectRequest(rid=i, values=vals[i * 2:(i + 1) * 2],
                          accuracy=acc[i * 2:(i + 1) * 2],
                          p_claim=pq[i * 2:(i + 1) * 2]) for i in range(4)]
    svc = DetectionService(sc5.dataset, p5, CFG, mode="bucketed", tile=64,
                           max_batch_requests=8, result_cache=False)

    def flush_all(rs):
        futs = [svc.submit(r) for r in rs]
        svc.flush()
        return [f.result() for f in futs]

    flush_all(reqs)                      # builds the cache (one full gather)
    builds0 = svc.engine.last_stats["mask_full_builds"]
    cvals, cacc, cpq, _ = synthetic_query_rows(sc5, 4, seed=9)
    svc.commit(cvals, cacc, cpq)

    calls = {"n": 0}
    real = tilecache.chunk_block_inc

    def counted(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    tilecache.chunk_block_inc = counted
    try:
        served = flush_all(reqs[:2])
    finally:
        tilecache.chunk_block_inc = real
    st = svc.engine.last_stats
    cache = svc.engine._mask_cache
    full_cells = cache.block_inc.size
    updated = st["mask_blocks_updated"]
    emit(f"pipeline/S512/dev{n_dev}/commit_detect_regathers", calls["n"],
         f"mask_source={st['mask_source']}")
    emit(f"pipeline/S512/dev{n_dev}/commit_detect_mask_cells", updated,
         f"full_rebuild_cells={full_cells}")
    assert calls["n"] == 0, \
        f"commit→detect regathered {calls['n']} full chunks"
    assert st["mask_source"] == "cache" and st["mask_full_builds"] == builds0
    assert 0 < updated < full_cells, \
        f"mask work {updated} not O(touched) vs full {full_cells}"
    assert all(r.copying.shape[0] == 2 for r in served)

    # ---- 4. (tile × chunk_group) autotune, cached for `scaling` -----------
    def timed(tile, group):
        eng = _engine("bucketed", tile=tile, chunk_group=group)
        eng.detect(sc5.dataset, p5, index=idx5)       # warm-up (JIT compile)
        return min(eng.detect(sc5.dataset, p5, index=idx5).wall_time_s
                   for _ in range(2))

    won = autotune(timed, tiles=(128, 256), groups=(1, 2), force=True)
    emit(f"pipeline/autotune/{won['backend']}/tile", won["tile"],
         f"chunk_group={won['chunk_group']} wall={won['wall_s']}s "
         f"sweep={len(won['sweep'])}pts")


def kernel():
    """Copyscore tile-path microbenchmark (ISSUE 2).

    Times the legacy two-orientation dataflow (one single-direction
    copyscore_tile per ORDERED kept tile + a separate full-incidence non-Ē
    matmul) against the fused triangular path (one dual-direction
    copyscore_tile_fused per UNORDERED tile), at f32/bf16 and int8 incidence.
    Asserts the triangular schedule bound (tiles ≤ (n_blocks² + n_blocks)/2)
    and that engine decisions still equal the exact INDEX — CI runs this as a
    smoke step.
    """
    import jax
    import jax.numpy as jnp
    from repro.core.bucketed import pad_buckets
    from repro.core.distributed import _local_tile_scores
    from repro.core.index import bucketize_engine
    from repro.data.claims import oracle_claim_probs, synthetic_claims
    from repro.kernels.ops import copyscore_tile

    S = 2048
    sc = synthetic_claims(SCALING_SPECS[S])
    p = oracle_claim_probs(sc)
    idx = build_index(sc.dataset, p, CFG)
    eng = _engine("bucketed", tile=256)
    bucketed, p_lo, p_hi = bucketize_engine(idx, 64)
    delta = eng._bucket_deltas(bucketed.p_hat, p_lo, p_hi, sc.dataset.accuracy)
    T = eng._tile_edge(S)
    n_blocks = -(-S // T)
    S_pad = n_blocks * T
    acc_pad = np.pad(sc.dataset.accuracy.astype(np.float32), (0, S_pad - S),
                     constant_values=0.5)

    rr, cc = np.meshgrid(np.arange(n_blocks), np.arange(n_blocks),
                         indexing="ij")
    ordered = np.stack([rr.ravel(), cc.ravel()], 1).astype(np.int32)
    tri = ordered[ordered[:, 0] <= ordered[:, 1]]
    tri_bound = (n_blocks * n_blocks + n_blocks) // 2
    assert len(tri) <= tri_bound, (len(tri), tri_bound)
    emit("kernel/S2048/tiles_triangular", len(tri),
         f"ordered={len(ordered)} bound={tri_bound}")

    def legacy_scan(v_skw, acc, p_hat, d, coords, *, tile, ebar_bucket, impl):
        """The pre-fused dataflow: single-direction kernel per ordered tile
        plus a separate non-Ē incidence matmul (what PR 1 shipped)."""
        S_pad, K, w = v_skw.shape
        e_out = ebar_bucket * w

        def one_tile(_, rc):
            vr = jax.lax.dynamic_slice(
                v_skw, (rc[0] * tile, 0, 0), (tile, K, w)).reshape(tile, K * w)
            vc = jax.lax.dynamic_slice(
                v_skw, (rc[1] * tile, 0, 0), (tile, K, w)).reshape(tile, K * w)
            a_r = jax.lax.dynamic_slice(acc, (rc[0] * tile,), (tile,))
            a_c = jax.lax.dynamic_slice(acc, (rc[1] * tile,), (tile,))
            c, n, err = copyscore_tile(vr, vc, p_hat, a_r, a_c, s=CFG.s,
                                       n_false=CFG.n, block_i=128, block_j=128,
                                       block_e=w, impl=impl, delta_blk=d)
            n_out = jnp.dot(vr[:, :e_out].astype(jnp.float32),
                            vc[:, :e_out].astype(jnp.float32).T,
                            preferred_element_type=jnp.float32)
            return 0, (c, n, n_out, err)

        return jax.lax.scan(one_tile, 0, coords)[1]

    def timed(fn, *args):
        out = fn(*args)                                # warm-up (JIT compile)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    base_dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    base_name = "bf16" if base_dt == jnp.bfloat16 else "f32"
    for dt, dt_name in ((base_dt, base_name), (jnp.int8, "int8")):
        padded = pad_buckets(bucketed, dtype=dt)
        v_np = np.asarray(padded.v_ksw)
        v_skw = np.moveaxis(v_np, 0, 1)
        if S_pad > S:
            v_skw = np.concatenate(
                [v_skw, np.zeros((S_pad - S,) + v_skw.shape[1:], v_np.dtype)])
        args = (jnp.asarray(v_skw), jnp.asarray(acc_pad),
                jnp.asarray(padded.p_hat), jnp.asarray(delta))
        common = dict(tile=T, ebar_bucket=padded.ebar_bucket, impl="auto")
        nout = jnp.asarray(
            (np.arange(padded.n_buckets) < padded.ebar_bucket), jnp.float32)
        legacy = jax.jit(lambda *a: legacy_scan(*a, **common))
        fused = jax.jit(lambda *a: _local_tile_scores(
            *a, tile=T, s=CFG.s, n=CFG.n, impl="auto",
            block_i=128, block_j=128))
        t_leg = timed(legacy, *args, jnp.asarray(ordered))
        t_fus = timed(fused, *args, nout, jnp.asarray(tri))
        emit(f"kernel/S2048/legacy_{dt_name}/seconds", round(t_leg, 3),
             f"tiles={len(ordered)}")
        emit(f"kernel/S2048/fused_{dt_name}/seconds", round(t_fus, 3),
             f"tiles={len(tri)} speedup={t_leg / max(t_fus, 1e-9):.2f}x")

    # decision cross-check: triangular engine == exact INDEX (S=512 so the
    # entry-sequential reference stays tractable)
    sc5 = synthetic_claims(SCALING_SPECS[512])
    p5 = oracle_claim_probs(sc5)
    exact = _engine("exact").detect(sc5.dataset, p5)
    eng5 = _engine("bucketed", tile=128)
    res = eng5.detect(sc5.dataset, p5)
    st = eng5.last_stats
    nb5 = -(-sc5.dataset.n_sources // st["tile"])
    assert st["tiles_kept"] <= (nb5 * nb5 + nb5) // 2, st
    match = bool(np.array_equal(res.copying, exact.copying))
    assert match, "triangular engine decisions diverged from exact INDEX"
    emit("kernel/S512/decisions_match_exact", int(match),
         f"tiles={st['tiles_kept']}/{st['tiles_total']}")


def serve():
    """Batched serving benchmark (ISSUE 3): requests/sec + latency vs batch
    size, plus sampled-vs-exact decision agreement.

    A 256-source corpus serves 24 requests of 4 query rows each through
    ``core/serving.serve_batch`` at batch sizes 1 / 2 / 8 (one tiled engine
    pass per batch). Asserts that batched decisions equal the per-request
    ones (DESIGN.md §5) and that ``sample_verify`` decisions equal the exact
    INDEX on its candidate set (DESIGN.md §4) — CI runs this as a smoke step
    under 1 and 8 virtual devices. Request latency is modeled as an
    all-at-once burst: every request is pending at t0, so a request's
    latency is the cumulative wall time through its batch.
    """
    import jax
    from repro.core.serving import DetectRequest, serve_batch
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
        synthetic_query_rows,
    )

    S, D, n_req, q = 256, 1024, 24, 4
    sc = synthetic_claims(SyntheticSpec(
        n_sources=S, n_items=D, coverage="book", n_cliques=6, clique_size=3,
        clique_items=12, seed=0))
    p = oracle_claim_probs(sc)
    vals, acc, pq, _ = synthetic_query_rows(sc, n_req * q, seed=1)
    requests = [DetectRequest(rid=i, values=vals[i * q:(i + 1) * q],
                              accuracy=acc[i * q:(i + 1) * q],
                              p_claim=pq[i * q:(i + 1) * q])
                for i in range(n_req)]
    eng = _engine("bucketed")
    n_dev = len(jax.devices())

    def run_batched(bs):
        groups = [requests[i: i + bs] for i in range(0, n_req, bs)]
        for g in groups:                      # warm-up (JIT compile per shape)
            serve_batch(sc.dataset, p, eng, g)
        responses, latencies = [], []
        t0 = time.perf_counter()
        for g in groups:
            responses.extend(serve_batch(sc.dataset, p, eng, g))
            elapsed = time.perf_counter() - t0
            latencies.extend([elapsed] * len(g))
        return time.perf_counter() - t0, responses, np.asarray(latencies)

    base_dt = None
    base_responses = None
    for bs in (1, 2, 8):
        dt, responses, lat = run_batched(bs)
        emit(f"serve/S{S}/dev{n_dev}/batch{bs}/requests_per_s",
             round(n_req / dt, 2),
             f"p50={np.percentile(lat, 50) * 1e3:.0f}ms "
             f"p99={np.percentile(lat, 99) * 1e3:.0f}ms")
        if bs == 1:
            base_dt, base_responses = dt, responses
        else:
            match = all(
                np.array_equal(b.copying, s.copying)
                and np.array_equal(b.intra_copying, s.intra_copying)
                for b, s in zip(responses, base_responses))
            assert match, f"batch={bs} decisions diverged from per-request"
            emit(f"serve/S{S}/dev{n_dev}/batch{bs}/decisions_match_per_request",
                 int(match))
            if bs == 8:
                emit(f"serve/S{S}/dev{n_dev}/batch8/speedup_vs_batch1",
                     round(base_dt / dt, 2))

    # sampled-vs-exact agreement: sample_verify candidate decisions must
    # equal the exact INDEX; overall F vs exact measures the net's recall
    exact = _engine("exact").detect(sc.dataset, p)
    sv = _engine("sample_verify", sample_rate=0.1, min_per_source=4,
                 sample_seed=1)
    res = sv.detect(sc.dataset, p)
    cand = sv._last_considered
    agree = bool((res.copying[cand] == exact.copying[cand]).all())
    assert agree, "sample_verify decisions diverged from exact on candidates"
    _, _, f = pair_f_measure(res.copying_pairs(), exact.copying_pairs())
    emit(f"serve/S{S}/sample_verify/candidate_agreement", int(agree),
         f"candidates={sv.last_stats['candidate_pairs']} "
         f"slack={sv.last_stats['slack_final']}")
    emit(f"serve/S{S}/sample_verify/f_vs_exact", round(f, 3),
         f"sampled_items={sv.last_stats['items_sampled']}")


def store():
    """Chunked CorpusStore scenario (ISSUE 4): serve_batch host-copy bytes
    and req/s BEFORE (legacy per-batch union concatenation) vs AFTER (one
    preallocated resident store, query rows written in place), plus the
    engine's chunk-stream telemetry under a chunk-bytes cap. Decisions must
    be identical on both paths — CI runs this as a smoke step.
    """
    import jax
    from repro.core import ClaimsDataset
    from repro.core.serving import DetectRequest, ResidentCorpus, serve_batch
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
        synthetic_query_rows,
    )

    S, D, n_req, q, bs = 256, 1024, 16, 4, 8
    sc = synthetic_claims(SyntheticSpec(
        n_sources=S, n_items=D, coverage="book", n_cliques=6, clique_size=3,
        clique_items=12, seed=0))
    p = oracle_claim_probs(sc)
    vals, acc, pq, _ = synthetic_query_rows(sc, n_req * q, seed=1)
    requests = [DetectRequest(rid=i, values=vals[i * q:(i + 1) * q],
                              accuracy=acc[i * q:(i + 1) * q],
                              p_claim=pq[i * q:(i + 1) * q])
                for i in range(n_req)]
    groups = [requests[i: i + bs] for i in range(0, n_req, bs)]
    eng = _engine("bucketed")
    n_dev = len(jax.devices())

    def run_legacy():
        """The pre-resident dataflow: concatenate the union every batch."""
        copied = 0
        responses = []
        for g in groups:
            values = np.concatenate([sc.dataset.values]
                                    + [r.values for r in g])
            a = np.concatenate([sc.dataset.accuracy] + [r.accuracy for r in g])
            pp = np.concatenate([p] + [r.p_claim for r in g])
            copied += values.nbytes + a.nbytes + pp.nbytes
            union = ClaimsDataset(values=values, accuracy=a)
            res = eng.detect(union, pp)
            off = S
            for r in g:
                responses.append(res.copying[off: off + r.n_rows, :S].copy())
                off += r.n_rows
        return copied, responses

    def run_resident(rc):
        copied = 0
        responses = []
        for g in groups:
            out = serve_batch(sc.dataset, p, eng, g, resident=rc)
            copied += out[0].host_copy_bytes
            responses.extend(o.copying for o in out)
        return copied, responses

    rc = ResidentCorpus(sc.dataset, p, max_query_rows=bs * q)
    run_legacy()                                   # warm-up (JIT compile)
    run_resident(rc)

    def best_of(fn, reps=3):
        """Fastest of ``reps`` runs — engine compute dominates at this
        corpus size, so a single sample is scheduler noise."""
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, out)
        return best[1] + (best[0],)

    bytes_legacy, resp_legacy, t_legacy = best_of(run_legacy)
    bytes_res, resp_res, t_res = best_of(lambda: run_resident(rc))

    match = all(np.array_equal(a, b) for a, b in zip(resp_legacy, resp_res))
    assert match, "resident-store decisions diverged from the legacy concat"
    # staged bytes shrink from O((S+q)·D) to O(q·D) per batch — the factor
    # is ≈ (S + q_batch)/q_batch (9× at this corpus/batch shape, unbounded
    # as the corpus grows)
    assert bytes_res < bytes_legacy / 5, (bytes_res, bytes_legacy)
    emit(f"store/S{S}/dev{n_dev}/legacy/host_copy_bytes_per_batch",
         bytes_legacy // len(groups), f"req_per_s={n_req / t_legacy:.2f}")
    emit(f"store/S{S}/dev{n_dev}/resident/host_copy_bytes_per_batch",
         bytes_res // len(groups), f"req_per_s={n_req / t_res:.2f}")
    emit(f"store/S{S}/dev{n_dev}/host_copy_reduction",
         round(bytes_legacy / max(bytes_res, 1), 1),
         f"decisions_match={int(match)}")

    # chunk-stream telemetry under a chunk-bytes cap: peak resident
    # incidence (host chunks AND per-pass device groups) stays under the cap
    cap = 256 << 10
    idx = build_index(sc.dataset, p, CFG, chunk_bytes=cap)
    eng2 = _engine("bucketed", chunk_group_bytes=cap)
    res2 = eng2.detect(sc.dataset, p, index=idx)
    st = eng2.last_stats
    assert idx.store.max_chunk_nbytes <= cap
    assert st["peak_group_bytes"] <= cap
    exact = _engine("exact").detect(sc.dataset, p, index=idx)
    agree = bool(np.array_equal(res2.copying, exact.copying))
    assert agree, "capped-store engine decisions diverged from exact"
    emit(f"store/S{S}/chunk_cap_bytes", cap,
         f"chunks={idx.store.n_chunks} max_chunk={idx.store.max_chunk_nbytes}")
    emit(f"store/S{S}/engine_peak_group_bytes", st["peak_group_bytes"],
         f"chunk_tiles={st['chunk_tiles_run']}/{st['chunk_tiles_total']} "
         f"decisions_match_exact={int(agree)}")


def mutate():
    """Live corpus mutation scenario (ISSUE 5): delta-chunk commits vs full
    re-index rebuilds, and cached serving across commits.

    A 256-source corpus takes a stream of commits whose rows claim only the
    UPPER half of the item axis, while a zipf-skewed request mix claims only
    the LOWER half — so no commit can touch an entry any cached pair shares,
    and the invalidation-aware ResultCache keeps serving across epochs
    (an epoch-keyed cache would drop everything). Asserts:

      * ``commit_rows`` ≥ 5× faster than ``build_index`` over the union;
      * commit+detect (mutation path, cache on) ≥ 5× faster than
        rebuild+detect (fresh index + uncached passes) per wave;
      * decisions after the full commit schedule equal a rebuild from the
        union claim set, for the served mix AND fresh probe requests.
    """
    import jax
    from repro.core import build_index
    from repro.core.index import commit_rows as index_commit
    from repro.core.serving import DetectRequest, DetectionService, serve_batch
    from repro.core.types import ClaimsDataset
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
    )

    S, D, q = 256, 1024, 8
    n_pool, n_waves, mix_per_wave = 6, 3, 12
    sc = synthetic_claims(SyntheticSpec(
        n_sources=S, n_items=D, coverage="book", n_cliques=6, clique_size=3,
        clique_items=12, seed=0))
    p = oracle_claim_probs(sc)
    n_dev = len(jax.devices())
    rng = np.random.default_rng(7)
    n_false = int(max(sc.dataset.values.max(), 1))

    def rows_on(lo, hi, n_rows, copy_of=None):
        """Query rows claiming only items in [lo, hi); optionally copiers."""
        vals = -np.ones((n_rows, D), np.int32)
        for r in range(n_rows):
            if copy_of is not None:
                o = int(rng.integers(0, S))
                o_idx = np.nonzero(sc.dataset.values[o, lo:hi] >= 0)[0] + lo
                take = o_idx[rng.random(o_idx.size) < 0.8]
                vals[r, take] = sc.dataset.values[o, take]
            idx = lo + rng.choice(hi - lo, size=24, replace=False)
            idx = idx[vals[r, idx] < 0]
            correct = rng.random(idx.size) < 0.7
            vals[r, idx] = np.where(correct, 0,
                                    rng.integers(1, n_false + 1, idx.size))
        acc = np.full(n_rows, 0.7, np.float32)
        pc = np.where(vals == 0, 0.95,
                      np.where(vals > 0, 0.02, 0.0)).astype(np.float32)
        return vals, acc, pc

    # request pool on the lower item half (half of them corpus copiers)
    pool = []
    for i in range(n_pool):
        vals, acc, pc = rows_on(0, D // 2, q,
                                copy_of=(i % 2 == 0) or None)
        pool.append(DetectRequest(rid=i, values=vals, accuracy=acc, p_claim=pc))
    # zipf-skewed mix over the pool, fixed across waves
    mix_ids = (rng.zipf(1.5, size=n_waves * mix_per_wave) - 1) % n_pool
    commits = [rows_on(D // 2, D, q) for _ in range(n_waves)]

    # ---- 1. raw index maintenance: commit_rows vs build_index rebuild -----
    idx = build_index(sc.dataset, p, CFG, row_capacity=S + n_waves * q)
    union_vals, union_acc, union_p = sc.dataset.values, sc.dataset.accuracy, p
    t_commit_total = t_rebuild_total = 0.0
    for vals, acc, pc in commits:
        union_vals = np.concatenate([union_vals, vals])
        union_acc = np.concatenate([union_acc, acc])
        union_p = np.concatenate([union_p, pc])
        union = ClaimsDataset(values=union_vals, accuracy=union_acc)
        t0 = time.perf_counter()
        info = index_commit(idx, union, union_p, CFG, q, compact=False)
        t_commit_total += time.perf_counter() - t0
        t0 = time.perf_counter()
        idx_rebuilt = build_index(union, union_p, CFG)
        t_rebuild_total += time.perf_counter() - t0
    speedup = t_rebuild_total / max(t_commit_total, 1e-9)
    emit(f"mutate/S{S}/dev{n_dev}/commit_ms_per_wave",
         round(t_commit_total / n_waves * 1e3, 2),
         f"bits={info.bits_set} new_entries={info.new_entries} "
         f"delta_chunks={idx.store.n_delta_chunks}")
    emit(f"mutate/S{S}/dev{n_dev}/rebuild_ms_per_wave",
         round(t_rebuild_total / n_waves * 1e3, 2),
         f"speedup={speedup:.1f}x")
    assert speedup >= 5.0, (t_commit_total, t_rebuild_total)
    # the committed index must decide exactly like the rebuilt one
    eng_c, eng_r = _engine("bucketed", tile=64), _engine("bucketed", tile=64)
    union = ClaimsDataset(values=union_vals, accuracy=union_acc)
    res_c = eng_c.detect(union, union_p, index=idx)
    res_r = eng_r.detect(union, union_p, index=idx_rebuilt)
    match = bool(np.array_equal(res_c.copying, res_r.copying))
    assert match, "committed-index decisions diverged from rebuild"
    emit(f"mutate/S{S}/dev{n_dev}/decisions_match_rebuild", int(match),
         f"entries={idx.store.n_live_entries}")

    # ---- 2. end-to-end: commit+detect vs rebuild+detect -------------------
    svc = DetectionService(sc.dataset, p, CFG, mode="bucketed", tile=64,
                           max_batch_requests=8, max_pending_rows=256)
    for r in pool:                                    # warm-up + JIT compile
        svc.submit(r)
    svc.flush()
    svc.stats = type(svc.stats)()

    def serve_mix(target, wave):
        ids = mix_ids[wave * mix_per_wave: (wave + 1) * mix_per_wave]
        futs = [target.submit(pool[i]) for i in ids]
        target.flush()
        return [f.result() for f in futs]

    corpus_v, corpus_a, corpus_p = (sc.dataset.values, sc.dataset.accuracy, p)
    t_mutate = 0.0
    t_rebuild = 0.0
    resp_a = []
    resp_b = []
    for wave, (vals, acc, pc) in enumerate(commits):
        corpus_v = np.concatenate([corpus_v, vals])
        corpus_a = np.concatenate([corpus_a, acc])
        corpus_p = np.concatenate([corpus_p, pc])
        # path A — the mutation path: commit into the live service, then
        # serve the wave's skewed mix (repeats hit the ResultCache)
        t0 = time.perf_counter()
        svc.commit(vals, acc, pc)
        resp_a.append(serve_mix(svc, wave))
        t_mutate += time.perf_counter() - t0
        # path B — the rebuild path: fresh index over the grown corpus (a
        # new service == build_index + resident copy), uncached passes
        t0 = time.perf_counter()
        cold = DetectionService(
            ClaimsDataset(values=corpus_v, accuracy=corpus_a), corpus_p, CFG,
            mode="bucketed", tile=64, max_batch_requests=8,
            result_cache=False)
        resp_b.append(serve_mix(cold, wave))
        t_rebuild += time.perf_counter() - t0
    st = svc.stats
    e2e = t_rebuild / max(t_mutate, 1e-9)
    emit(f"mutate/S{S}/dev{n_dev}/commit_detect_s", round(t_mutate, 3),
         f"cache_hit_rate={st.cache_hit_rate:.2f} hits={st.cache_hits} "
         f"misses={st.cache_misses}")
    emit(f"mutate/S{S}/dev{n_dev}/rebuild_detect_s", round(t_rebuild, 3),
         f"speedup={e2e:.1f}x")
    assert st.cache_hit_rate > 0.5, st
    assert e2e >= 5.0, (t_mutate, t_rebuild)
    emit(f"mutate/S{S}/dev{n_dev}/commit_detect_speedup", round(e2e, 1),
         f"bar=5.0 waves={n_waves}")

    # ---- 3. served decisions equal the rebuild path, wave by wave ---------
    agree = all(
        np.array_equal(a.copying, b.copying)
        and np.array_equal(a.intra_copying, b.intra_copying)
        for wa, wb in zip(resp_a, resp_b) for a, b in zip(wa, wb))
    assert agree, "cached/committed serving diverged from rebuild"
    # fresh probes (never cached) against the final corpus
    probe_vals, probe_acc, probe_p = rows_on(0, D, q, copy_of=True)
    probe = DetectRequest(rid=99, values=probe_vals, accuracy=probe_acc,
                          p_claim=probe_p)
    fut = svc.submit(probe)
    svc.flush()
    a = fut.result()
    eng = _engine("bucketed", tile=64)
    b = serve_batch(ClaimsDataset(values=corpus_v, accuracy=corpus_a),
                    corpus_p, eng, [probe])[0]
    probe_match = bool(np.array_equal(a.copying, b.copying))
    assert probe_match, "probe decisions diverged from rebuild"
    emit(f"mutate/S{S}/dev{n_dev}/served_decisions_match_rebuild",
         int(agree and probe_match),
         f"cache_invalidations={st.cache_invalidations}")


def durability():
    """Durable service scenario (ISSUE 6): snapshot/restore vs rebuild.

    A durable 768-source service takes a stream of commits (each fsync'd
    into the commit log) and serves a request mix, snapshotting on the way.
    Measures:

      * restore wall-clock (latest snapshot + log-tail replay) vs
        rebuild-from-claims (a fresh ``DetectionService`` over the union
        corpus — ``build_index`` dominant), ≥ 5× asserted;
      * raw replay rate in commits/s, from a second state dir that keeps
        only the initial snapshot (``snapshot_every=0``) so restore replays
        the ENTIRE commit history through the in-memory commit path;
      * decisions of the restored service asserted equal to the
        never-restarted one — served mix, fresh probes, and ServiceStats
        epochs (the BENCH_durability.json acceptance row).
    """
    import os
    import shutil
    import tempfile

    import jax
    from repro.core import DurabilityOptions
    from repro.core.serving import DetectRequest, DetectionService
    from repro.core.types import ClaimsDataset
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
    )

    S, D, q = 768, 2048, 8
    n_waves = 6
    sc = synthetic_claims(SyntheticSpec(
        n_sources=S, n_items=D, coverage="book", n_cliques=20, clique_size=3,
        clique_items=12, seed=0))
    p = oracle_claim_probs(sc)
    n_dev = len(jax.devices())
    rng = np.random.default_rng(11)
    n_false = int(max(sc.dataset.values.max(), 1))

    def make_rows(n_rows, copy_of=None):
        vals = -np.ones((n_rows, D), np.int32)
        for r in range(n_rows):
            if copy_of is not None:
                o = int(rng.integers(0, S))
                o_idx = np.nonzero(sc.dataset.values[o] >= 0)[0]
                take = o_idx[rng.random(o_idx.size) < 0.8]
                vals[r, take] = sc.dataset.values[o, take]
            idx = rng.choice(D, size=24, replace=False)
            idx = idx[vals[r, idx] < 0]
            correct = rng.random(idx.size) < 0.7
            vals[r, idx] = np.where(correct, 0,
                                    rng.integers(1, n_false + 1, idx.size))
        acc = np.full(n_rows, 0.7, np.float32)
        pc = np.where(vals == 0, 0.95,
                      np.where(vals > 0, 0.02, 0.0)).astype(np.float32)
        return vals, acc, pc

    commits = [make_rows(q) for _ in range(n_waves)]
    probes = [DetectRequest(rid=i, values=v, accuracy=a, p_claim=pc)
              for i, (v, a, pc) in
              enumerate(make_rows(4, copy_of=(i % 2 == 0) or None)
                        for i in range(3))]

    def serve_all(svc):
        futs = [svc.submit(r) for r in probes]
        svc.flush()
        return [f.result() for f in futs]

    dir_snap = tempfile.mkdtemp(prefix="bench-durability-snap-")
    dir_log = tempfile.mkdtemp(prefix="bench-durability-log-")
    try:
        # snapshot_every lands a snapshot exactly at the last commit, so the
        # restore measured below is snapshot-load dominated (the hot path)
        svc = DetectionService(
            sc.dataset, p, CFG, mode="bucketed", tile=64,
            durability=DurabilityOptions(state_dir=dir_snap,
                                         snapshot_every=n_waves // 2))
        # second service: initial snapshot ONLY → restore replays every
        # commit; same schedule, so both state dirs describe the same corpus
        svc_log = DetectionService(
            sc.dataset, p, CFG, mode="bucketed", tile=64,
            durability=DurabilityOptions(state_dir=dir_log, snapshot_every=0))
        t0 = time.perf_counter()
        for vals, acc, pc in commits:
            svc.commit(vals, acc, pc)
        t_commit = time.perf_counter() - t0
        for vals, acc, pc in commits:
            svc_log.commit(vals, acc, pc)
        live_resp = serve_all(svc)                    # never-restarted ref

        # ---- restore (snapshot hot path) vs rebuild-from-claims ----------
        t0 = time.perf_counter()
        restored = DetectionService.restore(dir_snap)
        t_restore = time.perf_counter() - t0
        union_v = np.concatenate([sc.dataset.values] + [c[0] for c in commits])
        union_a = np.concatenate([sc.dataset.accuracy] + [c[1] for c in commits])
        union_p = np.concatenate([p] + [c[2] for c in commits])
        t0 = time.perf_counter()
        DetectionService(ClaimsDataset(values=union_v, accuracy=union_a),
                         union_p, CFG, mode="bucketed", tile=64)
        t_rebuild = time.perf_counter() - t0
        speedup = t_rebuild / max(t_restore, 1e-9)
        ri = restored.restore_info
        emit(f"durability/S{S}/dev{n_dev}/commit_ms_per_wave",
             round(t_commit / n_waves * 1e3, 2),
             f"fsync=commit waves={n_waves} log_bytes="
             f"{os.path.getsize(os.path.join(dir_log, 'commits.wal'))}")
        emit(f"durability/S{S}/dev{n_dev}/restore_ms",
             round(t_restore * 1e3, 2),
             f"snapshot_epoch={ri.snapshot_epoch} "
             f"replayed={ri.replayed_commits}")
        emit(f"durability/S{S}/dev{n_dev}/rebuild_ms",
             round(t_rebuild * 1e3, 2), f"speedup={speedup:.1f}x")
        assert speedup >= 5.0, (t_restore, t_rebuild)
        emit(f"durability/S{S}/dev{n_dev}/restore_speedup",
             round(speedup, 1), "bar=5.0")

        # ---- raw replay rate (log-only state dir) -------------------------
        replayed = DetectionService.restore(dir_log)
        rr = replayed.restore_info
        assert rr.replayed_commits == n_waves, rr
        emit(f"durability/S{S}/dev{n_dev}/replay_commits_per_s",
             round(rr.replayed_commits / max(rr.replay_s, 1e-9), 1),
             f"replayed={rr.replayed_commits} replay_s={rr.replay_s:.3f}")

        # ---- restored decisions == never-restarted ------------------------
        assert restored.epoch == replayed.epoch == svc.epoch
        assert restored.stats.commits == svc.stats.commits
        for other in (restored, replayed):
            resp = serve_all(other)
            for a, b in zip(live_resp, resp):
                assert np.array_equal(a.copying, b.copying)
                assert np.array_equal(a.intra_copying, b.intra_copying)
        emit(f"durability/S{S}/dev{n_dev}/decisions_match_restored", 1,
             f"epoch={restored.epoch} probes={len(probes)}")
    finally:
        shutil.rmtree(dir_snap, ignore_errors=True)
        shutil.rmtree(dir_log, ignore_errors=True)


def overload():
    """Traffic-hardening scenario (ISSUE 7, DESIGN.md §9): what happens at
    2× capacity, and how degraded replicas and retractions behave.

    Four legs:

      1. unloaded baseline — sequential single-request latency (p99) and
         batched capacity (req/s at batch 8), the reference the overload
         SLO is defined against;
      2. 2× overload — a mixed commit/retract/read arrival stream at twice
         the measured capacity, every read carrying a deadline of 1.5× the
         unloaded p99. Admission control + queue expiry shed the excess
         with typed errors and the adaptive batch limit trades batching
         for latency; asserts the p99 of admitted-and-met requests stays
         ≤ 1.5× the unloaded p99 and that shedding actually engaged
         (before this PR the same stream piled onto the queue until every
         caller waited out the flat 30 s submit timeout — the cliff
         BENCH_serve.json's 11.5 req/s at batch 8 turns into);
      3. circuit breaker — a replica failing 5 consecutive commits trips
         its breaker (first 4 waves abort fleet-wide, classic rollback);
         the fleet keeps committing without it, two more writes queue in
         its backlog, and after the cooldown one probe write replays the
         backlog and rejoins the replica at epoch equality — asserted;
      4. retraction — retract-then-detect equals a service rebuilt without
         the retracted sources (asserted), with the wall-clock of both.
    """
    import importlib.util
    import pathlib

    import jax
    from repro.core.serving import (
        DeadlineExceeded,
        DetectRequest,
        DetectionService,
        ReplicaBroadcastError,
        ReplicaRouter,
        ServiceOverloaded,
    )
    from repro.core.types import ClaimsDataset
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
        synthetic_query_rows,
    )

    faults_path = (pathlib.Path(__file__).resolve().parent.parent
                   / "tests" / "faults.py")
    spec_ = importlib.util.spec_from_file_location("_bench_faults", faults_path)
    faults = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(faults)

    S, D, q = 256, 1024, 4
    sc = synthetic_claims(SyntheticSpec(
        n_sources=S, n_items=D, coverage="book", n_cliques=6, clique_size=3,
        clique_items=12, seed=0))
    p = oracle_claim_probs(sc)
    n_dev = len(jax.devices())
    rng = np.random.default_rng(17)
    n_pool = 64
    vals, acc, pq, _ = synthetic_query_rows(sc, n_pool * q, seed=2)

    def req(i, deadline_s=None):
        j = i % n_pool
        return DetectRequest(rid=i, values=vals[j * q:(j + 1) * q],
                             accuracy=acc[j * q:(j + 1) * q],
                             p_claim=pq[j * q:(j + 1) * q],
                             deadline_s=deadline_s)

    def wave(n_rows=2):
        w = np.where(rng.random((n_rows, D)) < 0.03,
                     rng.integers(0, 3, (n_rows, D)), -1).astype(np.int32)
        a = rng.uniform(0.5, 0.9, n_rows).astype(np.float32)
        pc = np.where(w == 0, 0.9,
                      np.where(w > 0, 0.05, 0.0)).astype(np.float32)
        return w, a, pc

    # ---- 1. unloaded baseline: p99 (sequential) + capacity (batched) ------
    svc = DetectionService(sc.dataset, p, CFG, mode="bucketed", tile=64,
                           max_batch_requests=8, max_pending_rows=256,
                           result_cache=False)
    for i in range(8):                                # warm-up (JIT compile)
        svc.submit(req(i))
    svc.flush()
    lat_u = []
    for i in range(12):
        f = svc.submit(req(100 + i))
        svc.flush()
        lat_u.append(f.result().latency_s)
    p99_u = float(np.percentile(lat_u, 99))
    n_cap = 16
    t0 = time.perf_counter()
    futs = [svc.submit(req(200 + i)) for i in range(n_cap)]
    svc.flush()
    [f.result() for f in futs]
    capacity = n_cap / (time.perf_counter() - t0)
    emit(f"overload/S{S}/dev{n_dev}/unloaded_p99_ms", round(p99_u * 1e3, 1),
         f"capacity_req_per_s={capacity:.1f}")

    # ---- 2. mixed commit/retract/read stream at 2× capacity ---------------
    deadline = 1.5 * p99_u
    n_over = 80
    interval = 1.0 / (2.0 * capacity)
    svc.stats = type(svc.stats)()
    svc.start()
    futs, shed, rejected, writes = [], 0, 0, 0
    t0 = time.perf_counter()
    for i in range(n_over):
        if i % 10 == 5:
            svc.commit(*wave())
            writes += 1
        elif i % 10 == 9 and svc.resident.n_corpus > S:
            n = svc.resident.n_corpus
            svc.retract([n - 2, n - 1])
            writes += 1
        try:
            futs.append(svc.submit(req(1000 + i, deadline_s=deadline),
                                   timeout=5.0))
        except DeadlineExceeded:
            shed += 1
        except ServiceOverloaded:
            rejected += 1
        t_next = t0 + (i + 1) * interval
        time.sleep(max(0.0, t_next - time.perf_counter()))
    svc.stop()
    t_wall = time.perf_counter() - t0
    met, missed = [], []
    for f in futs:
        try:
            r = f.result(timeout=60)
            (met if r.latency_s <= deadline else missed).append(r.latency_s)
        except DeadlineExceeded:
            shed += 1
    st = svc.stats
    assert len(met) + len(missed) + shed + rejected == n_over
    assert shed > 0, "2x overload must shed load (cliff otherwise)"
    assert met, "overload shed everything — no admitted requests at all"
    p99_adm = float(np.percentile(met, 99))
    assert p99_adm <= deadline * 1.001, (p99_adm, deadline)
    emit(f"overload/S{S}/dev{n_dev}/2x/admitted_req_per_s",
         round(len(met) / t_wall, 2),
         f"writes={writes} wall_s={t_wall:.1f}")
    emit(f"overload/S{S}/dev{n_dev}/2x/admitted_p99_ms",
         round(p99_adm * 1e3, 1),
         f"bar={deadline * 1e3:.1f}ms missed_deadline={len(missed)}")
    emit(f"overload/S{S}/dev{n_dev}/2x/shed_rate",
         round(shed / n_over, 3),
         f"shed={shed} rejected={rejected} "
         f"arrival_shed={st.shed} queue_expired={st.expired}")
    emit(f"overload/S{S}/dev{n_dev}/2x/adaptive_batch",
         svc._batch_limit,
         f"shrinks={st.batch_shrinks} grows={st.batch_grows} "
         f"queue_wait_p99_ms={st.queue_wait_p99 * 1e3:.1f}")

    # ---- 3. circuit breaker: 5 consecutive commit faults ------------------
    router = ReplicaRouter(sc.dataset, p, CFG, n_replicas=2, mode="bucketed",
                           tile=64, breaker_threshold=5,
                           breaker_cooldown_s=5.0, result_cache=False)
    clock = faults.FakeClock()
    router.breakers[1]._clock = clock
    aborted = 0
    with faults.failing_writes(router.replicas[1]) as fault:
        while router.stats.breaker_trips == 0:
            try:
                router.commit(*wave())
            except ReplicaBroadcastError:
                aborted += 1
        assert aborted == 4, aborted          # failures 1–4 abort fleet-wide
        assert router.epoch == 1              # failure 5 trips → fleet commits
        assert router.replicas[1].epoch == 0
        router.commit(*wave())                # buffered: breaker open
        router.retract([S])                   # retraction buffers too
        # backlog: trip-wave commit (ejected, fleet applied) + both above
        assert len(router._backlogs[1]) == 3
        fault["left"] = 0                     # replica healed
    clock.advance(6.0)                        # cooldown elapses → probe
    router.commit(*wave())                    # catch-up: 3 backlog ops + live
    assert router.replicas[0].epoch == router.replicas[1].epoch == 4
    rst = router.stats
    assert rst.breaker_trips == 1 and rst.breaker_open == 0
    assert not router._backlogs[1]
    emit(f"overload/S{S}/dev{n_dev}/breaker/recovered_epoch",
         router.replicas[1].epoch,
         f"aborted_waves={aborted} trips={rst.breaker_trips} "
         f"backlog_replayed=3 open_now={rst.breaker_open}")

    # ---- 4. retraction == rebuild-without-source --------------------------
    svc_r = DetectionService(sc.dataset, p, CFG, mode="bucketed", tile=64)
    probes = [req(9000 + i) for i in range(3)]
    row_ids = [5, 77, 130]
    t0 = time.perf_counter()
    info = svc_r.retract(row_ids)
    t_retract = time.perf_counter() - t0
    futs = [svc_r.submit(r) for r in probes]
    svc_r.flush()
    resp_a = [f.result() for f in futs]
    keep = np.setdiff1d(np.arange(S), row_ids)
    t0 = time.perf_counter()
    ref = DetectionService(
        ClaimsDataset(values=sc.dataset.values[keep],
                      accuracy=sc.dataset.accuracy[keep]),
        p[keep], CFG, mode="bucketed", tile=64, result_cache=False)
    t_rebuild = time.perf_counter() - t0
    futs = [ref.submit(r) for r in probes]
    ref.flush()
    resp_b = [f.result() for f in futs]
    match = all(np.array_equal(a.copying, b.copying)
                and np.array_equal(a.intra_copying, b.intra_copying)
                for a, b in zip(resp_a, resp_b))
    assert match, "retract-then-detect diverged from rebuild-without-source"
    emit(f"overload/S{S}/dev{n_dev}/retract_ms", round(t_retract * 1e3, 2),
         f"rows={info.rows} touched={info.touched_entries} "
         f"gc={info.gc_entries}")
    emit(f"overload/S{S}/dev{n_dev}/retract_vs_rebuild_speedup",
         round(t_rebuild / max(t_retract, 1e-9), 1),
         f"rebuild_ms={t_rebuild * 1e3:.1f} decisions_match={int(match)}")


def lm():
    """Training-substrate throughput smoke (tiny llama on CPU)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import Model
    from repro.optim import adamw
    from repro.optim.schedule import warmup_cosine
    from repro.runtime.train_loop import init_train_state, make_train_step

    cfg = get_config("llama3.2-1b").reduced(d_model=64, d_ff=128, vocab=256)
    model = Model(cfg)
    opt = adamw()
    step = jax.jit(make_train_step(model, opt, warmup_cosine(1e-3, 5, 100)))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    B, S = 8, 128
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 256, (B, S)), jnp.int32)}
    state, _ = step(state, batch)                     # compile
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        state, m = step(state, batch)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / n
    emit("lm/train_step/us_per_call", round(dt * 1e6, 1),
         f"tokens_per_s={B * S / dt:.0f}")


# default order: cheapest first so partial runs still cover most tables
TABLES = {
    "lm": lm, "fig2": fig2, "fig3": fig3, "store": store, "mutate": mutate,
    "durability": durability, "serve": serve, "overload": overload,
    "scaling": scaling, "multihost": multihost, "pipeline": pipeline,
    "kernel": kernel,
    "table8": table8, "table9": table9,
    "table10": table10, "table6": table6, "table7": table7,
}


def write_bench_json(which, durations) -> str:
    """BENCH_<run>.json: rows + environment, for perf-trajectory diffing."""
    import jax

    run = "all" if list(which) == list(TABLES) else "-".join(which)
    out = {
        "run": run,
        "generated_unix": int(time.time()),
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "tables": {k: {"duration_s": round(v, 2)} for k, v in durations.items()},
        "rows": {name: {"value": value, "derived": derived}
                 for name, value, derived in ROWS},
    }
    path = f"BENCH_{run}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return path


def main() -> None:
    from repro.runtime.platform import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    FLAGS.update(a for a in args if a.startswith("--"))
    which = [a for a in args if not a.startswith("--")] or list(TABLES)
    print("name,value,derived")
    durations = {}
    for w in which:
        t0 = time.perf_counter()
        TABLES[w]()
        durations[w] = time.perf_counter() - t0
        print(f"# {w} done in {durations[w]:.1f}s", flush=True)
    path = write_bench_json(which, durations)
    print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
