"""Serving CLI: LM decoding and copy-detection serving.

  --task lm (default): batched greedy decoding with KV/SSM caches.

      PYTHONPATH=src python -m repro.launch.serve --arch falcon-mamba-7b \
          --reduced --batch 4 --prompt-len 16 --new-tokens 32

  --task detect: the batched detection service (core/serving.py,
      DESIGN.md §5). A corpus is held in memory; concurrent requests — each
      a few query sources to be checked for copying against the corpus —
      are drained from a bounded queue and folded into ONE tiled
      DetectionEngine pass per batch, with per-request scatter of the
      decision matrix and backpressure at the submit edge. Run with
      XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise the
      sharded tile path on CPU.

      PYTHONPATH=src python -m repro.launch.serve --task detect \
          --sources 512 --items 1536 --requests 32 --batch-requests 8

      --mode sample_verify serves the sample-then-verify engine
      (DESIGN.md §4) instead of the exact bucketed path.

      --commit-accepted exercises the corpus-mutation path end-to-end
      (DESIGN.md §7): after the first wave, every served request's rows are
      committed into the live corpus (delta-chunk re-index, no rebuild) and
      the wave is re-served — repeats hit the invalidation-aware result
      cache — then ServiceStats (cache hit rate, delta-chunk count,
      re-index/compaction counters) are printed. --replicas N serves through
      a ReplicaRouter with epoch-consistent commit broadcast.

      --deadline-s attaches a per-request deadline (DESIGN.md §9): requests
      the admission controller predicts cannot be served in time are shed
      at submit, queued requests whose deadline passes expire typed, and
      the adaptive batch limit shrinks under pressure. Queue-wait
      percentiles, shed/expired counts, and the final batch limit are
      printed. --breaker-threshold / --breaker-cooldown-s tune the
      per-replica commit circuit breaker when --replicas > 1.

      --retract-last N retracts the N newest corpus rows after the serve
      (and after --commit-accepted, if given) and prints the retraction
      receipt — rows unwound, index entries touched/GC'd, cache
      invalidations — demonstrating the membership-unwind path without a
      rebuild.

      --state-dir makes the service durable (DESIGN.md §8, OPERATIONS.md):
      commits append to a fsync'd commit log and full snapshots land every
      --snapshot-every commits. When the directory already holds a manifest
      the service is RESTORED from it — latest valid snapshot + log-tail
      replay — instead of built from the synthetic corpus, and the restore
      receipt (snapshot epoch, replayed commits, discarded torn-tail bytes)
      is printed. With --replicas each replica persists under its own
      replica-<i>/ subdirectory.

      At exit it prints the per-span totals of ``repro.utils.trace``: the
      host time of every named step of the passes, and the bytes shipped to
      the device (OPERATIONS.md, "Reading the spans").
"""
from __future__ import annotations

import argparse
import time


def serve_lm(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import Model
    from repro.models.model import greedy_decode

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    cond = None
    if cfg.cond_len:
        cond = jnp.asarray(rng.normal(0, 1, (args.batch, cfg.cond_len,
                                             cfg.cond_dim)), jnp.float32)
    t0 = time.time()
    out = greedy_decode(model, params, prompts, args.new_tokens, cond=cond)
    dt = time.time() - t0
    total = args.batch * (args.prompt_len + args.new_tokens)
    print(f"[serve] {out.shape} tokens in {dt:.1f}s "
          f"({total / dt:.0f} tok/s incl. compile)")
    print(out[:, :16])


def serve_detect(args):
    import os

    import jax
    import numpy as np
    from repro.core import CopyConfig, DurabilityOptions
    from repro.core.serving import (
        DeadlineExceeded,
        DetectRequest,
        DetectionService,
        ReplicaRouter,
        ServiceOverloaded,
    )
    from repro.data.claims import (
        SyntheticSpec,
        oracle_claim_probs,
        synthetic_claims,
        synthetic_query_rows,
    )

    cfg = CopyConfig(alpha=0.1, s=0.8, n=50.0)
    spec = SyntheticSpec(n_sources=args.sources, n_items=args.items,
                         coverage="book", n_cliques=max(3, args.sources // 40),
                         clique_size=3, clique_items=12, seed=0)
    sc = synthetic_claims(spec)
    p = oracle_claim_probs(sc)
    q = args.rows_per_request
    vals, acc, pq, origins = synthetic_query_rows(
        sc, args.requests * q, seed=1)
    requests = [
        DetectRequest(rid=i, values=vals[i * q:(i + 1) * q],
                      accuracy=acc[i * q:(i + 1) * q],
                      p_claim=pq[i * q:(i + 1) * q],
                      deadline_s=args.deadline_s)
        for i in range(args.requests)
    ]
    service_kw = dict(
        mode=args.mode,
        max_batch_requests=args.batch_requests,
        max_pending_rows=args.max_pending_rows,
        tile=args.tile, devices=args.devices,
        prefetch_depth=args.prefetch_depth)
    if args.shards and args.shards > 1:
        # row-range-sharded corpus plane (DESIGN.md §10): each detection
        # pass scans per shard and merges; spill/bitpack bound residency
        service_kw.update(
            n_shards=args.shards, shard_pack=args.shard_pack,
            shard_spill_bytes=args.shard_spill_bytes,
            shard_spill_dir=args.shard_spill_dir)
    if args.mesh_shape:
        d, pod = (int(x) for x in args.mesh_shape.split("x"))
        service_kw["mesh_shape"] = (d, pod)
    if args.state_dir:
        service_kw["durability"] = DurabilityOptions(
            state_dir=args.state_dir, snapshot_every=args.snapshot_every)
    restorable = (args.state_dir and args.replicas <= 1
                  and not args.shard_owners and os.path.exists(
                      os.path.join(args.state_dir, "manifest.json")))
    if restorable:
        svc = DetectionService.restore(args.state_dir,
                                       devices=args.devices)
        ri = svc.restore_info
        print(f"[serve] restored {args.state_dir}: snapshot epoch "
              f"{ri.snapshot_epoch} + {ri.replayed_commits} replayed "
              f"commits in {ri.wall_s:.2f}s "
              f"({ri.discarded_bytes} torn-tail bytes discarded); "
              f"corpus {svc.resident.n_corpus} sources at epoch {svc.epoch}")
    elif args.shard_owners:
        # shard-owner fleet (DESIGN.md §12): each replica OWNS one row
        # range of a single shared sharded index; tiled fan-out modes
        # scatter the scan per owner and merge on the router
        svc = ReplicaRouter(sc.dataset, p, cfg,
                            shard_owners=args.shard_owners,
                            breaker_threshold=args.breaker_threshold,
                            breaker_cooldown_s=args.breaker_cooldown_s,
                            shard_pack=args.shard_pack,
                            shard_spill_bytes=args.shard_spill_bytes,
                            shard_spill_dir=args.shard_spill_dir,
                            **{k: v for k, v in service_kw.items()
                               if k not in ("n_shards", "shard_pack",
                                            "shard_spill_bytes",
                                            "shard_spill_dir")})
        print(f"[serve] shard-owner fleet: {args.shard_owners} owners, "
              f"placement {svc._owner_plan().bounds.tolist()}")
    elif args.replicas > 1:
        svc = ReplicaRouter(sc.dataset, p, cfg, n_replicas=args.replicas,
                            breaker_threshold=args.breaker_threshold,
                            breaker_cooldown_s=args.breaker_cooldown_s,
                            **service_kw)
    else:
        svc = DetectionService(sc.dataset, p, cfg, **service_kw)
    print(f"[serve] corpus {args.sources}×{args.items}, mode={args.mode}, "
          f"devices={args.devices or len(jax.devices())}, "
          f"replicas={args.replicas}, "
          f"batch≤{args.batch_requests} requests, "
          f"backpressure at {args.max_pending_rows} rows")

    def _services(s):
        return s.replicas if isinstance(s, ReplicaRouter) else [s]

    def _reset(s):
        # fresh stats AND caches so the timed run measures engine passes,
        # not warm-up leftovers
        for one in _services(s):
            one.stats = type(one.stats)()
            if one.cache is not None:
                one.cache = type(one.cache)(one.cache.max_entries)

    # warm-up with one full-size batch (the largest union shape) so the
    # timed run mostly excludes JIT compilation — odd-sized batches the
    # worker happens to drain can still compile once; capped at the
    # pending-row budget (nothing drains until the flush); reset stats so
    # the printed passes/mean-batch describe only the timed run
    n_warm = max(1, min(args.batch_requests, args.max_pending_rows // q))
    for r in requests[:n_warm]:
        # deadline-free clone: a tight --deadline-s must not shed the
        # warm-up, whose whole point is to absorb JIT compilation
        svc.submit(DetectRequest(rid=f"warm-{r.rid}", values=r.values,
                                 accuracy=r.accuracy, p_claim=r.p_claim))
    svc.flush()
    _reset(svc)

    shed = expired = 0
    t0 = time.perf_counter()
    with svc:
        pairs = []
        for r in requests:
            try:
                pairs.append((r, svc.submit(r)))
            except (DeadlineExceeded, ServiceOverloaded):
                shed += 1
        served, results = [], []
        for r, f in pairs:
            try:
                results.append(f.result())
                served.append(r)
            except DeadlineExceeded:
                expired += 1
    dt = time.perf_counter() - t0

    hits = planted = 0
    for r, resp in zip(served, results):
        for row in range(q):
            o = int(origins[r.rid * q + row])
            if o >= 0:
                planted += 1
                hits += int(resp.copying[row, o])
    print(f"[serve] {len(results)}/{len(requests)} requests in {dt:.2f}s "
          f"({len(results) / dt:.1f} req/s), "
          f"{svc.stats.batches} engine passes "
          f"(mean batch {svc.stats.mean_batch:.1f})")
    if results:
        lat = np.array([r.latency_s for r in results])
        print(f"[serve] latency p50={np.percentile(lat, 50) * 1e3:.0f} ms "
              f"p99={np.percentile(lat, 99) * 1e3:.0f} ms; "
              f"planted copiers detected {hits}/{planted}")
    if args.shards and args.shards > 1:
        es = _services(svc)[0].engine.last_stats
        print(f"[serve] shard plane: {es.get('n_shards')} shards "
              f"{es.get('shard_plan')}, peak resident/shard "
              f"{es.get('shard_peak_resident_bytes')} bytes, "
              f"mesh={es.get('mesh_shape') or '1-D'}")
    if args.deadline_s is not None:
        st = svc.stats
        limits = [s._batch_limit for s in _services(svc)]
        print(f"[serve] deadline {args.deadline_s * 1e3:.0f} ms: "
              f"{shed} shed at submit, {expired} expired in queue; "
              f"queue wait p50={st.queue_wait_p50 * 1e3:.0f} ms "
              f"p99={st.queue_wait_p99 * 1e3:.0f} ms; "
              f"batch limit {max(limits)} "
              f"({st.batch_shrinks} shrinks, {st.batch_grows} grows)")

    if args.commit_accepted:
        # fold the ACCEPTED rows into the live corpus — rows detection
        # cleared of copying (copier rows are rejected; independent rows
        # carry fresh evidence) — then re-serve the same wave: repeats whose
        # claims no commit touched come straight from the result cache
        t0 = time.perf_counter()
        n_acc = 0
        for r, resp in zip(served, results):
            keep = ~resp.copying.any(axis=1) & ~resp.intra_copying.any(axis=1)
            if keep.any():
                svc.commit(r.values[keep], r.accuracy[keep], r.p_claim[keep])
                n_acc += int(keep.sum())
        t_commit = time.perf_counter() - t0
        t0 = time.perf_counter()
        with svc:
            futs = []
            for r in requests:
                try:
                    futs.append(svc.submit(r))
                except (DeadlineExceeded, ServiceOverloaded):
                    pass
            for f in futs:
                try:
                    f.result()
                except DeadlineExceeded:
                    pass
        t_wave2 = time.perf_counter() - t0
        st = svc.stats
        corpus_rows = max(s.resident.n_corpus for s in _services(svc))
        print(f"[serve] committed {n_acc} accepted rows in {t_commit:.2f}s "
              f"({st.commits} commits, corpus now {corpus_rows} sources); "
              f"re-served wave in {t_wave2:.2f}s")
        print(f"[serve] ServiceStats: cache_hit_rate="
              f"{st.cache_hit_rate:.1%} ({st.cache_hits} hits / "
              f"{st.cache_misses} misses, "
              f"{st.cache_invalidations} invalidations), "
              f"delta_chunks={st.delta_chunks}, "
              f"new_entries={st.new_entries}, "
              f"reindexed_entries={st.reindexed_entries}, "
              f"compactions={st.compactions}")

    if args.retract_last:
        n = max(s.resident.n_corpus for s in _services(svc))
        k = min(args.retract_last, n - 1)
        row_ids = list(range(n - k, n))
        t0 = time.perf_counter()
        out = svc.retract(row_ids)
        t_retract = time.perf_counter() - t0
        info = (next(i for i in out if i is not None)
                if isinstance(out, list) else out)
        st = svc.stats
        print(f"[serve] retracted {info.rows} newest rows in "
              f"{t_retract * 1e3:.1f} ms: {info.touched_entries} index "
              f"entries re-scored, {info.gc_entries} GC'd, "
              f"{st.cache_invalidations} cache invalidations; corpus now "
              f"{max(s.resident.n_corpus for s in _services(svc))} sources "
              f"at epoch {max(s.epoch for s in _services(svc))}")
        if args.replicas > 1:
            print(f"[serve] breaker: trips={st.breaker_trips} "
                  f"open_now={st.breaker_open}")

    from repro.utils import trace
    print("[serve] host spans and counters of this run:")
    print(trace.table(trace.records()))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=("lm", "detect"), default="lm")
    # lm args
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    # detect args
    ap.add_argument("--sources", type=int, default=256)
    ap.add_argument("--items", type=int, default=1024)
    ap.add_argument("--mode", default="bucketed",
                    help="DetectionEngine mode (bucketed, sample_verify, ...)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rows-per-request", type=int, default=4)
    ap.add_argument("--batch-requests", type=int, default=8,
                    help="requests folded into one engine pass")
    ap.add_argument("--max-pending-rows", type=int, default=256,
                    help="backpressure bound on queued query rows")
    ap.add_argument("--tile", type=int, default=256)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="chunk groups the async pipeline stages ahead of "
                         "the tile kernel (DESIGN.md §11); 0 = synchronous")
    ap.add_argument("--platform", default=None,
                    help="JAX platform (cpu/gpu/tpu); on gpu also enables "
                         "the latency-hiding scheduler XLA flags")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="virtual host CPU devices "
                         "(--xla_force_host_platform_device_count)")
    ap.add_argument("--shards", type=int, default=None,
                    help="row-range shards of the corpus data plane "
                         "(DESIGN.md §10); each detection pass scans per "
                         "shard and merges bit-equal to unsharded")
    ap.add_argument("--shard-pack", action="store_true",
                    help="bitpack shard chunk blocks to 1 bit/entry "
                         "during scans (8x over int8)")
    ap.add_argument("--shard-spill-bytes", type=int, default=None,
                    help="per-shard resident byte cap; cold blocks spill "
                         "to checksummed frames (LRU)")
    ap.add_argument("--shard-spill-dir", default=None,
                    help="spill directory (default: a temp dir when a "
                         "byte cap is set)")
    ap.add_argument("--mesh-shape", default=None,
                    help="2-D tile mesh DATAxPOD (e.g. 4x2): tiles over "
                         "data, entry chunks over pod")
    ap.add_argument("--commit-accepted", action="store_true",
                    help="after the first wave, commit every served "
                         "request's rows into the live corpus (delta-chunk "
                         "re-index) and re-serve the wave; prints "
                         "ServiceStats incl. cache hit rate")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds: hopeless "
                         "requests are shed at submit, stale queued ones "
                         "expire typed (DESIGN.md §9)")
    ap.add_argument("--retract-last", type=int, default=0,
                    help="after serving, retract the N newest corpus rows "
                         "and print the retraction receipt")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a ReplicaRouter with this many "
                         "DetectionService replicas (commits broadcast)")
    ap.add_argument("--shard-owners", type=int, default=None,
                    help="shard-owner fleet (DESIGN.md §12): this many "
                         "replicas, each OWNING one row range of a shared "
                         "sharded index; tiled fan-out modes scatter the "
                         "scan per owner and the router merges partial "
                         "grids bit-equal to a single host")
    ap.add_argument("--breaker-threshold", type=int, default=5,
                    help="consecutive commit failures before a replica's "
                         "circuit breaker opens and it is ejected from "
                         "the broadcast (--replicas > 1)")
    ap.add_argument("--breaker-cooldown-s", type=float, default=5.0,
                    help="seconds an open breaker waits before probing "
                         "the replica with a catch-up replay")
    ap.add_argument("--state-dir", default=None,
                    help="durable state directory (commit log + snapshots); "
                         "restored from when it already holds a manifest")
    ap.add_argument("--snapshot-every", type=int, default=16,
                    help="write a full snapshot every N commits "
                         "(0 = only the initial snapshot)")
    args = ap.parse_args()
    # platform/flag setup must precede the first JAX call (the task
    # functions import jax lazily, so this is early enough)
    from repro.runtime.platform import (enable_compile_cache,
                                        set_host_device_count, set_platform)
    if args.platform:
        set_platform(args.platform)
    if args.host_devices:
        set_host_device_count(args.host_devices)
    enable_compile_cache()
    if args.task == "detect":
        serve_detect(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
