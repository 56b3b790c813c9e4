"""Platform setup + pipeline autotuning for launch and benchmarks.

Four concerns the tiled engine (DESIGN.md §6, §11) pushes to process
startup:

* **Host allocator** — ``keep_host_arrays_on_heap`` has glibc serve the
  chunk-sized arrays a pass allocates and frees from its heap, so later
  passes reuse their pages instead of faulting fresh ones in.

* **Persistent compilation cache** — ``enable_compile_cache`` keeps
  compiled programs across processes: in ``JAX_COMPILATION_CACHE_DIR``
  when that is set, else at one fixed directory of the checkout.

* **XLA platform/flag setup** — ``set_platform`` selects the backend and,
  on GPU, turns on the latency-hiding scheduler + async collectives so the
  prefetcher's host→device copies overlap the running tile kernel at the
  XLA level too. Must run before the first JAX call (flags are read at
  backend init).
* **Per-backend pipeline autotuning** — the best (tile edge, chunk_group)
  point depends on the backend (CPU wants cache-sized groups, accelerators
  want dispatch-amortizing ones), so ``autotune`` sweeps a caller-provided
  timing function over a small grid once and caches the winner in
  ``<cache_dir>/<backend>.json``; ``load_autotune`` lets later runs (e.g.
  ``benchmarks.run scaling``) adopt it without re-sweeping.
"""
from __future__ import annotations

import ctypes
import json
import os
from pathlib import Path
from typing import Callable, Iterable, Optional

import jax

#: Default location of the per-backend autotune cache (relative to cwd).
AUTOTUNE_DIR = ".autotune"

#: Compilation cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: ``.jax_cache`` at the checkout root, found from this file and not from
#: the working directory. The path is part of the cache key, so it must not
#: move between runs.
COMPILE_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


#: glibc ``mallopt`` parameters (malloc.h).
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3
#: Host allocations under this size come from glibc's heap: a Book-full
#: pass's store chunks (about 1.7 MB) and its per-entry and per-row
#: temporaries.
HEAP_MMAP_BYTES = 32 << 20
#: Freed heap kept for reuse before glibc returns it to the system.
HEAP_TRIM_BYTES = 1 << 30
_heap_kept = False


def keep_host_arrays_on_heap() -> bool:
    """Serve host allocations under ``HEAP_MMAP_BYTES`` from glibc's heap,
    and keep up to ``HEAP_TRIM_BYTES`` of freed heap for reuse; once per
    process.

    A detection pass allocates and frees hundreds of store chunks and
    claim-sized temporaries. glibc maps each one afresh unless a freed
    mapping of a larger size has raised its threshold, so every pass
    page-faults them in again: on a TPU v5e host that made a Book-full
    index build take nearly twice as long (PERF.md, Findings). Returns
    False where the C library has no ``mallopt`` (not glibc).
    """
    global _heap_kept
    if not _heap_kept:
        try:
            mallopt = ctypes.CDLL(None).mallopt
        except (OSError, AttributeError):
            return False
        mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
        mallopt.restype = ctypes.c_int
        _heap_kept = bool(mallopt(_M_MMAP_THRESHOLD, HEAP_MMAP_BYTES)
                          and mallopt(_M_TRIM_THRESHOLD, HEAP_TRIM_BYTES))
    return _heap_kept


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``COMPILE_CACHE_DIR``.
    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def set_platform(platform: str = "cpu") -> None:
    """Select the JAX backend; on GPU, enable the latency-hiding flags.

    Only takes effect at the beginning of the program (XLA reads
    ``XLA_FLAGS`` when the backend initializes). The GPU flag set follows
    the upstream gpu_performance_tips guidance: async collectives and the
    latency-hiding scheduler let compiled collectives and host transfers
    overlap compute — the device-side complement of the engine's
    ``ChunkPrefetcher``.
    """
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        os.environ["XLA_FLAGS"] = (
            "--xla_gpu_enable_triton_softmax_fusion=true "
            "--xla_gpu_triton_gemm_any=True "
            "--xla_gpu_enable_async_collectives=true "
            "--xla_gpu_enable_latency_hiding_scheduler=true "
            "--xla_gpu_enable_highest_priority_async_stream=true "
        )


def set_host_device_count(n: int) -> None:
    """Expose ``n`` virtual devices on the host CPU platform.

    Appends (rather than overwrites) ``--xla_force_host_platform_device_
    count`` so it composes with ``set_platform``'s flag block. Only
    effective before the first JAX call.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if not f.startswith(
                         "--xla_force_host_platform_device_count"))
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n)}".strip())


def _cache_path(cache_dir: str) -> str:
    """Per-backend cache file — CPU and accelerator winners never collide."""
    return os.path.join(cache_dir, f"{jax.default_backend()}.json")


def load_autotune(cache_dir: str = AUTOTUNE_DIR) -> Optional[dict]:
    """Return the cached winner for the current backend, or None.

    The dict carries ``tile``, ``chunk_group``, ``wall_s`` and the full
    ``sweep`` it won (see ``autotune``). Corrupt/partial cache files read
    as None — the caller just falls back to defaults.
    """
    try:
        with open(_cache_path(cache_dir)) as f:
            out = json.load(f)
        if "tile" in out and "chunk_group" in out:
            return out
    except (OSError, ValueError):
        pass
    return None


def autotune(
    run_fn: Callable[[int, int], float],
    tiles: Iterable[int] = (128, 256),
    groups: Iterable[int] = (1, 2),
    cache_dir: str = AUTOTUNE_DIR,
    force: bool = False,
) -> dict:
    """Sweep ``run_fn(tile, chunk_group) → wall seconds``; cache the winner.

    A deliberately small grid — the knobs interact with backend memory
    hierarchy, not with correctness (every point produces bit-identical
    decisions), so a handful of timed points per backend suffices. Returns
    ``{"backend", "tile", "chunk_group", "wall_s", "sweep": [...]}`` and
    persists it at ``<cache_dir>/<backend>.json`` unless an existing cache
    already answers (``force=True`` re-sweeps).
    """
    if not force:
        cached = load_autotune(cache_dir)
        if cached is not None:
            return cached
    sweep = []
    for tile in tiles:
        for group in groups:
            wall = float(run_fn(int(tile), int(group)))
            sweep.append({"tile": int(tile), "chunk_group": int(group),
                          "wall_s": round(wall, 4)})
    best = min(sweep, key=lambda r: r["wall_s"])
    out = {"backend": jax.default_backend(), "tile": best["tile"],
           "chunk_group": best["chunk_group"], "wall_s": best["wall_s"],
           "sweep": sweep}
    os.makedirs(cache_dir, exist_ok=True)
    with open(_cache_path(cache_dir), "w") as f:
        json.dump(out, f, indent=2)
    return out


__all__ = ["AUTOTUNE_DIR", "COMPILE_CACHE_DIR", "HEAP_MMAP_BYTES",
           "HEAP_TRIM_BYTES", "autotune",
           "enable_compile_cache", "keep_host_arrays_on_heap",
           "load_autotune", "set_host_device_count", "set_platform"]
