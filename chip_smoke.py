"""Drive the copy-detection service end to end on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: tile meshes vs one chip

One chip: a ``DetectionService`` holds the paper's Book-full corpus (3,182
sources x 20,000 items, about 2.2 M claims, generated from seed 0) and runs
the Pallas kernel. It serves 16 detect requests of 4 rows each in batches of
8 after one warm-up batch, commits the served rows (one commit per request),
serves the wave again, retracts a few rows and serves it a third time.
Checks:

  * a second service on the same chip runs the jnp reference kernel
    (``kernel_impl="ref"``) over the same traffic and mutations, and every
    wave's ``copying`` and ``intra_copying`` must equal the Pallas service's;
  * the first 512 sources through ``bucketed`` (Pallas) must decide exactly
    as ``exact`` mode (the paper's INDEX) does;
  * the compiled tile scan must contain the Pallas kernel
    (``tpu_custom_call``).

Four chips: the same corpus and wave through the tile scan on a 4-device
1-D mesh and on a 2x2 ``data`` x ``pod`` mesh, each compared bit-equal
(decisions, shared counts n, counts outside the E-bar suffix) with a
one-device pass in the same process.

Diagnostics go to stdout as ``[smoke]`` lines; the last line is one JSON
object naming the device. Without a TPU, or when any phase fails or
disagrees with its reference, the script exits non-zero and prints no
result line. Everything runs in this one process: the chip belongs to the
process that first touches JAX.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ROWS_PER_REQUEST = 4
N_REQUESTS = 16
BATCH_REQUESTS = 8
SLICE_SOURCES = 512
FOUR_CHIP_DEVICES = 4         # the 1-D tile mesh of --chips 4
FOUR_CHIP_MESH = (2, 2)       # its data x pod mesh


class SmokeFailure(RuntimeError):
    """A phase ran but its output disagrees with its reference."""


def log(msg: str) -> None:
    """Print one diagnostic line."""
    print(f"[smoke] {msg}", flush=True)


def make_requests(sc, n_requests: int, seed: int):
    """``n_requests`` detect requests of ``ROWS_PER_REQUEST`` query rows
    each, plus the corpus source each row copies (-1 for independent rows)."""
    from repro.core.serving import DetectRequest
    from repro.data.claims import synthetic_query_rows

    q = ROWS_PER_REQUEST
    vals, acc, pq, origins = synthetic_query_rows(sc, n_requests * q,
                                                  seed=seed)
    reqs = [DetectRequest(rid=i, values=vals[i * q:(i + 1) * q],
                          accuracy=acc[i * q:(i + 1) * q],
                          p_claim=pq[i * q:(i + 1) * q])
            for i in range(n_requests)]
    return reqs, origins


def serve_wave(svc, requests):
    """Submit ``BATCH_REQUESTS`` at a time and drain each batch in this
    thread. Returns the responses and each batch's wall seconds."""
    out, walls = [], []
    for b0 in range(0, len(requests), BATCH_REQUESTS):
        futs = [svc.submit(r) for r in requests[b0:b0 + BATCH_REQUESTS]]
        t0 = time.perf_counter()
        svc.flush()
        walls.append(time.perf_counter() - t0)
        out.extend(f.result() for f in futs)
    return out, walls


def check_responses(name: str, got, n_corpus: int) -> None:
    """Every response has the expected shapes and finite scores."""
    import numpy as np

    for r in got:
        q = r.copying.shape[0]
        if (r.copying.shape != (q, n_corpus)
                or r.intra_copying.shape != (q, q)
                or r.c_fwd.shape != (q, n_corpus)):
            raise SmokeFailure(f"{name}: request {r.rid} has shapes "
                               f"{r.copying.shape}, {r.intra_copying.shape}")
        if not np.isfinite(r.c_fwd).all():
            raise SmokeFailure(f"{name}: request {r.rid} has non-finite C")


def check_same_decisions(name: str, got, want) -> None:
    """``copying`` and ``intra_copying`` of two response lists are equal."""
    import numpy as np

    for a, b in zip(got, want, strict=True):
        if not (np.array_equal(a.copying, b.copying)
                and np.array_equal(a.intra_copying, b.intra_copying)):
            diff = int((a.copying != b.copying).sum())
            raise SmokeFailure(f"{name}: request {a.rid} decisions differ "
                               f"({diff} corpus pairs)")


def scan_has_pallas_kernel(engine) -> bool:
    """Compile the engine's cached tile scan (its mesh, scores and kernel
    implementation) at a small fixed shape, two 256-row tiles over one
    512-entry chunk, and look for the Mosaic kernel call in the program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import _sharded_tile_fn

    tile, block, chunk = 256, 128, 512
    mesh = engine.mesh()
    fn = _sharded_tile_fn(mesh, tile, engine.cfg.s, engine.cfg.n,
                          engine.options.kernel_impl, block, block)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    lowered = fn.lower(sds((2 * tile, 1, chunk), jnp.int8),
                       sds((2 * tile,), jnp.float32),
                       sds((1,), jnp.float32), sds((1,), jnp.float32),
                       sds((1,), jnp.float32),
                       sds((mesh.size, 2), jnp.int32,
                           P(mesh.axis_names[0])))
    return "tpu_custom_call" in lowered.compile().as_text()


def one_chip(spec, impl: str = "pallas",
             slice_sources: int = SLICE_SOURCES) -> None:
    """The one-chip phases (module docstring); raises on any mismatch. The
    compiled scan is checked for the Mosaic kernel when ``impl`` is
    ``"pallas"``."""
    import numpy as np

    from repro.core import CopyConfig
    from repro.core.engine import DetectionEngine
    from repro.core.serving import DetectionService
    from repro.core.types import ClaimsDataset
    from repro.data.claims import oracle_claim_probs, synthetic_claims

    cfg = CopyConfig(alpha=0.1, s=0.8, n=50.0)
    sc = synthetic_claims(spec)
    ds, p = sc.dataset, oracle_claim_probs(sc)
    log(f"corpus {ds.n_sources} sources x {ds.n_items} items, "
        f"{int((ds.values >= 0).sum())} claims (seed {spec.seed})")
    kw = dict(mode="bucketed", devices=1, max_batch_requests=BATCH_REQUESTS)

    t0 = time.perf_counter()
    svc = DetectionService(ds, p, cfg, kernel_impl=impl, **kw)
    log(f"index build {time.perf_counter() - t0:.3f} s")
    ref = DetectionService(ds, p, cfg, kernel_impl="ref", **kw)

    warm, _ = make_requests(sc, BATCH_REQUESTS, seed=2)
    requests, origins = make_requests(sc, N_REQUESTS, seed=1)
    _, walls = serve_wave(svc, warm)
    log(f"first batch (compile + detect) {walls[0]:.3f} s")
    serve_wave(ref, warm)

    wave1, walls = serve_wave(svc, requests)
    check_responses("wave 1", wave1, svc.resident.n_corpus)
    check_same_decisions("wave 1 pallas vs ref", wave1,
                         serve_wave(ref, requests)[0])
    st = svc.engine.last_stats
    log(f"warm detect: {len(walls)} batches of {BATCH_REQUESTS} requests, "
        f"{', '.join(f'{w:.3f}' for w in walls)} s each; last pass "
        f"{st['chunks']} chunks of {st['chunk_width']} entries, "
        f"{st['tiles_kept']}/{st['tiles_total']} tiles, "
        f"{st['rescored_pairs']} pairs rescored exactly, staging "
        f"{st['staging_s']:.3f} s, kernel waited {st['stage_wait_s']:.3f} s "
        f"for staging")
    hits = planted = 0
    for r, resp in zip(requests, wave1):
        for row in range(ROWS_PER_REQUEST):
            o = int(origins[r.rid * ROWS_PER_REQUEST + row])
            if o >= 0:
                planted += 1
                hits += int(resp.copying[row, o])
    log(f"planted copiers detected {hits}/{planted}")

    # every served row is committed: at Book-full nearly every query row
    # shares a rare false value with some corpus source and is flagged, so
    # committing only the cleared rows would leave the write path idle
    cleared = sum(int((~resp.copying.any(axis=1)
                       & ~resp.intra_copying.any(axis=1)).sum())
                  for resp in wave1)
    commit_s = []
    for r in requests:
        t0 = time.perf_counter()
        svc.commit(r.values, r.accuracy, r.p_claim)
        commit_s.append(time.perf_counter() - t0)
        ref.commit(r.values, r.accuracy, r.p_claim)
    log(f"committed {svc.stats.committed_rows} served rows ({cleared} "
        f"cleared of copying) in {len(commit_s)} commits, "
        f"{', '.join(f'{c * 1e3:.1f}' for c in commit_s)} ms each")
    wave2, walls = serve_wave(svc, requests)
    check_responses("wave 2", wave2, svc.resident.n_corpus)
    check_same_decisions("wave 2 pallas vs ref", wave2,
                         serve_wave(ref, requests)[0])
    log(f"re-served after commits: "
        f"{', '.join(f'{w:.3f}' for w in walls)} s per batch, "
        f"{svc.stats.cache_hits} cache hits")

    n = svc.resident.n_corpus
    row_ids = [0, 1, n - 2, n - 1]
    t0 = time.perf_counter()
    svc.retract(row_ids)
    log(f"retracted rows {row_ids} in "
        f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
    ref.retract(row_ids)
    wave3, walls = serve_wave(svc, requests)
    check_responses("wave 3", wave3, svc.resident.n_corpus)
    check_same_decisions("wave 3 pallas vs ref", wave3,
                         serve_wave(ref, requests)[0])
    log(f"re-served after retraction: "
        f"{', '.join(f'{w:.3f}' for w in walls)} s per batch")
    log(f"pallas == ref decisions on 3 waves of {N_REQUESTS} requests")

    if impl == "pallas":
        if not scan_has_pallas_kernel(svc.engine):
            raise SmokeFailure("compiled tile scan has no tpu_custom_call")
        log("compiled tile scan contains tpu_custom_call")

    sub = ClaimsDataset(values=ds.values[:slice_sources],
                        accuracy=ds.accuracy[:slice_sources])
    p_sub = p[:slice_sources]
    tiled = DetectionEngine(cfg, mode="bucketed", devices=1,
                            kernel_impl=impl).detect(sub, p_sub)
    exact = DetectionEngine(cfg, mode="exact").detect(sub, p_sub)
    if not np.array_equal(tiled.copying, exact.copying):
        raise SmokeFailure(
            f"S={slice_sources}: bucketed and exact decisions differ "
            f"({int((tiled.copying != exact.copying).sum())} pairs)")
    log(f"S={slice_sources}: bucketed ({impl}) == exact, "
        f"{int(exact.copying.sum()) // 2} copying pairs")


def tile_grids(engine, ds, p):
    """The engine's tiled pass split open: (decisions, n, n_out)."""
    ctx = engine._tiled_prologue(ds, p)
    grids, run = engine._run_tiled_scan(ctx)
    _, n_cnt, n_out, _ = grids
    result = engine._tiled_finalize(ctx, grids, run)
    return result.copying, n_cnt, n_out


def four_chips(spec, impl: str = "pallas") -> None:
    """The four-chip phases (module docstring); raises on any mismatch."""
    import numpy as np

    from repro.core import CopyConfig
    from repro.core.engine import DetectionEngine
    from repro.core.serving import DetectionService
    from repro.data.claims import oracle_claim_probs, synthetic_claims

    cfg = CopyConfig(alpha=0.1, s=0.8, n=50.0)
    sc = synthetic_claims(spec)
    ds, p = sc.dataset, oracle_claim_probs(sc)
    log(f"corpus {ds.n_sources} sources x {ds.n_items} items")
    warm, _ = make_requests(sc, BATCH_REQUESTS, seed=2)
    requests, _ = make_requests(sc, N_REQUESTS, seed=1)
    rows, pods = FOUR_CHIP_MESH
    layouts = {"1 device": dict(devices=1),
               f"{FOUR_CHIP_DEVICES}-device 1-D mesh":
                   dict(devices=FOUR_CHIP_DEVICES),
               f"{rows}x{pods} data x pod mesh":
                   dict(mesh_shape=FOUR_CHIP_MESH)}
    base = None
    for name, layout in layouts.items():
        svc = DetectionService(ds, p, cfg, mode="bucketed", kernel_impl=impl,
                               max_batch_requests=BATCH_REQUESTS, **layout)
        _, walls = serve_wave(svc, warm)
        wave, warm_walls = serve_wave(svc, requests)
        check_responses(name, wave, ds.n_sources)
        eng = DetectionEngine(cfg, mode="bucketed", kernel_impl=impl,
                              **layout)
        grids = tile_grids(eng, ds, p)
        log(f"{name}: first batch {walls[0]:.3f} s, warm batches "
            f"{', '.join(f'{w:.3f}' for w in warm_walls)} s, "
            f"{int(grids[0].sum()) // 2} corpus copying pairs")
        if base is None:
            base = (wave, grids)
            continue
        check_same_decisions(f"{name} vs 1 device", wave, base[0])
        for what, a, b in zip(("decisions", "n", "n_out"), grids, base[1]):
            if not np.array_equal(a, b):
                raise SmokeFailure(f"{name} vs 1 device: {what} differ")
        log(f"{name} == 1 device: request decisions, corpus decisions, "
            f"n, n_out bit-equal")


def main(argv=None) -> int:
    """Run the phases for ``--chips``; 0 only when every check passed."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve/commit/retract on one chip; 4: the 1-D "
                         "and 2x2 tile meshes against one chip")
    args = ap.parse_args(argv)

    import jax

    from repro.data.claims import book_full_spec
    from repro.runtime.platform import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    log(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(book_full_spec(seed=0))
    else:
        one_chip(book_full_spec(seed=0))
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
