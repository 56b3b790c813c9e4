"""Distributed copy detection — sharded decompositions of the pair space.

The paper's §VIII names two parallelization opportunities ("per entry" and
"per pair of sources"). This module realizes both, at two granularities:

  * ``sharded_tile_scores`` — the DetectionEngine's production dataflow
    (DESIGN.md §3): the S×S pair space is cut into T×T tiles, tiles that
    survive the Ē pruning are round-robined over a 1-D device mesh with
    shard_map, and each device scans its tiles, slicing the bucket-aligned
    incidence and feeding the copyscore kernel one rectangular tile at a
    time. The incidence tensor is replicated (it is the small operand);
    only the tile list and the (n_tiles, T, T) outputs are sharded.

  * ``distributed_pair_scores`` — 2-D pair-space sharding over the
    production TPU mesh (launch/mesh.py): C-block rows over ``data``,
    columns over ``model`` (a SUMMA-like decomposition), with the entry
    dimension optionally sharded over ``pod`` and combined by one psum.

The incidence matrix V is passed twice with different shardings (row-block
copy and column-block copy); XLA lays each out once per device — there is no
gather of the full V anywhere.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scoring import score_same
from repro.core.types import CopyConfig
from repro.kernels.ops import copyscore_tile_fused
from repro.kernels.vma import zeros_varying_like


# ---------------------------------------------------------------------------
# 1-D tile sharding (DetectionEngine production path)
# ---------------------------------------------------------------------------

def _local_tile_scores(v_skw, acc, p_hat, delta, nout_blk, coords, *, tile,
                       s, n, impl, block_i, block_j):
    """Per-device: scan this shard's unordered pair tiles (fused dual kernel).

    v_skw:  (S_pad, K, w) chunk-aligned incidence, replicated — K chunks of
            the ``CorpusStore`` (one group of the engine's stream)
    nout_blk: (K,) float32 — 1.0 where the chunk lies before the Ē
            boundary (chunk handles carry this; the boundary is
            chunk-aligned by construction, so the channel is exact)
    coords: (n_local, 2) int32 — (row-block, col-block) indices of the tiles
            assigned to this device, r ≤ c (triangular schedule); (-1, -1)
            marks a padding slot — both mesh padding AND tiles chunk-pruned
            for this group — which produces zeros without any compute
    →       five (n_local, T, T) stacks: C_same→, C_same← (the mirrored
            tile's C→, transposed), shared count, count outside Ē (the
            considered test), and the approximation-error bound.
    """
    S_pad, K, w = v_skw.shape

    def compute(rc):
        r0 = rc[0] * tile
        c0 = rc[1] * tile
        vr = jax.lax.dynamic_slice(v_skw, (r0, 0, 0), (tile, K, w))
        vc = jax.lax.dynamic_slice(v_skw, (c0, 0, 0), (tile, K, w))
        a_r = jax.lax.dynamic_slice(acc, (r0,), (tile,))
        a_c = jax.lax.dynamic_slice(acc, (c0,), (tile,))
        return copyscore_tile_fused(
            vr.reshape(tile, K * w), vc.reshape(tile, K * w), p_hat, a_r, a_c,
            s=s, n_false=n, block_i=block_i, block_j=block_j, block_e=w,
            impl=impl, delta_blk=delta, nout_blk=nout_blk)

    def skip(rc):
        # typed like compute's outputs: lax.cond needs both branches to
        # vary over the same mesh axes
        return (zeros_varying_like((tile, tile), v_skw, acc, p_hat, delta,
                                   nout_blk, rc),) * 5

    def one_tile(_, rc):
        return 0, jax.lax.cond(rc[0] >= 0, compute, skip, rc)

    _, outs = jax.lax.scan(one_tile, 0, coords)
    return outs


def sharded_tile_scores(
    mesh: Mesh,
    v_skw,                   # (S_pad, K, w) incidence, S_pad % tile == 0
    acc,                     # (S_pad,) accuracies (0.5 in padding rows)
    p_hat,                   # (K,) representative p̂ per chunk
    coords: np.ndarray,      # (n_tiles, 2) int32 surviving (row, col) tiles
    cfg: CopyConfig,
    *,
    tile: int,
    delta: np.ndarray,       # (K,) per-chunk score-error bound δ
    nout: np.ndarray = None,  # (K,) 1.0 ⇔ chunk before the Ē boundary
    ebar_bucket: int | None = None,   # legacy alternative to ``nout``
    impl: str = "auto",
    block_i: int = 128,
    block_j: int = 128,
):
    """Shard surviving pair tiles over a 1-D mesh; returns stacked tiles.

    The incidence argument is one GROUP of chunk handles from the engine's
    stream — (S_pad, K, w) with per-chunk p̂ / δ / non-Ē arrays riding
    along — never the full matrix (DESIGN.md §6). ``coords`` lists
    unordered (r ≤ c) tiles and is padded to a multiple of the mesh size
    with (-1, -1) markers — padding slots (and tiles the caller chunk-pruned
    for this group) short-circuit to zero outputs inside the device scan
    (lax.cond) instead of recomputing a real tile. Output: five
    (n_tiles_padded, T, T) arrays (C_same→, C_same←, count, count outside
    Ē, error bound).
    """
    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    n_tiles = len(coords)
    K = v_skw.shape[1]
    if nout is None:
        eb = K if ebar_bucket is None else int(ebar_bucket)
        nout = (np.arange(K) < eb).astype(np.float32)
    pad = (-n_tiles) % n_dev
    if pad:
        coords = np.concatenate([coords,
                                 np.full((pad, 2), -1, coords.dtype)])

    fn = _sharded_tile_fn(mesh, tile, cfg.s, cfg.n, impl, block_i, block_j)
    return fn(jnp.asarray(v_skw), jnp.asarray(acc, jnp.float32),
              jnp.asarray(p_hat, jnp.float32),
              jnp.asarray(delta, jnp.float32),
              jnp.asarray(nout, jnp.float32),
              jnp.asarray(coords, jnp.int32))


@functools.lru_cache(maxsize=64)
def _sharded_tile_fn(mesh: Mesh, tile: int, s: float, n: float, impl: str,
                     block_i: int, block_j: int):
    """Cached jitted shard_map for the tile scan.

    The engine streams chunk groups through this in a host loop, so the
    compiled executable MUST be reused across calls — a fresh
    ``jax.jit(shard_map(...))`` per group would retrace every time.
    """
    axis = mesh.axis_names[0]
    local = partial(_local_tile_scores, tile=tile, s=s, n=n,
                    impl=impl, block_i=block_i, block_j=block_j)
    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(axis)),
        out_specs=(P(axis),) * 5,
    ))


# ---------------------------------------------------------------------------
# 2-D tile sharding: tiles over `data`, entry chunks over `pod`
# ---------------------------------------------------------------------------

def _local_tile_scores_2d(v_skw, acc, p_hat, delta, nout_blk, coords, *,
                          tile, s, n, impl, block_i, block_j, pod_axis):
    """Per-device: scan this data-shard's tiles over the local chunk shard,
    then one psum over ``pod`` combines the per-chunk partial channels."""
    outs = _local_tile_scores(v_skw, acc, p_hat, delta, nout_blk, coords,
                              tile=tile, s=s, n=n, impl=impl,
                              block_i=block_i, block_j=block_j)
    return tuple(jax.lax.psum(o, pod_axis) for o in outs)


def sharded_tile_scores_2d(
    mesh: Mesh,
    v_skw,                   # (S_pad, K, w) incidence, S_pad % tile == 0
    acc,                     # (S_pad,) accuracies (0.5 in padding rows)
    p_hat,                   # (K,) representative p̂ per chunk
    coords: np.ndarray,      # (n_tiles, 2) int32 surviving (row, col) tiles
    cfg: CopyConfig,
    *,
    tile: int,
    delta: np.ndarray,       # (K,) per-chunk score-error bound δ
    nout: np.ndarray = None,  # (K,) 1.0 ⇔ chunk before the Ē boundary
    impl: str = "auto",
    block_i: int = 128,
    block_j: int = 128,
):
    """Shard tiles over ``data`` AND entry chunks over ``pod`` (2-D mesh).

    Same contract as ``sharded_tile_scores``, but each pod member scans
    only its chunk slice of the group and one psum per channel combines
    the partial sums — so a group's resident incidence per device is
    K/pods chunks instead of K. Chunks are padded to a pod multiple with
    INERT chunks (zero incidence, δ = 0, non-Ē flag 0): a zero chunk
    contributes exactly zero to all five channels, so the padding never
    perturbs a result. The psum reorders float additions relative to the
    1-D stream, which the engine's rescore margin absorbs — decisions
    stay bit-equal (DESIGN.md §3.4, §10).
    """
    d_axis, p_axis = mesh.axis_names
    n_data = mesh.shape[d_axis]
    del n_data  # coords padding below keys off the mesh size directly
    n_pod = mesh.shape[p_axis]
    v_skw = np.asarray(v_skw)
    S_pad, K, w = v_skw.shape
    p_hat = np.asarray(p_hat, np.float32)
    delta = np.asarray(delta, np.float32)
    nout = (np.ones(K, np.float32) if nout is None
            else np.asarray(nout, np.float32))
    kpad = (-K) % n_pod
    if kpad:
        v_skw = np.concatenate(
            [v_skw, np.zeros((S_pad, kpad, w), v_skw.dtype)], axis=1)
        p_hat = np.concatenate([p_hat, np.full(kpad, 0.5, np.float32)])
        delta = np.concatenate([delta, np.zeros(kpad, np.float32)])
        nout = np.concatenate([nout, np.zeros(kpad, np.float32)])
    n_tiles = len(coords)
    pad = (-n_tiles) % mesh.shape[d_axis]
    if pad:
        coords = np.concatenate([coords,
                                 np.full((pad, 2), -1, coords.dtype)])
    fn = _sharded_tile_fn_2d(mesh, tile, cfg.s, cfg.n, impl,
                             block_i, block_j)
    v_dev = jax.device_put(v_skw, NamedSharding(mesh, P(None, p_axis, None)))
    return fn(v_dev, jnp.asarray(acc, jnp.float32),
              jnp.asarray(p_hat), jnp.asarray(delta), jnp.asarray(nout),
              jnp.asarray(coords, jnp.int32))


@functools.lru_cache(maxsize=64)
def _sharded_tile_fn_2d(mesh: Mesh, tile: int, s: float, n: float,
                        impl: str, block_i: int, block_j: int):
    """Cached jitted shard_map for the 2-D (data×pod) tile scan."""
    d_axis, p_axis = mesh.axis_names
    local = partial(_local_tile_scores_2d, tile=tile, s=s, n=n, impl=impl,
                    block_i=block_i, block_j=block_j, pod_axis=p_axis)
    return jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P(None, p_axis, None), P(), P(p_axis), P(p_axis),
                  P(p_axis), P(d_axis)),
        out_specs=(P(d_axis),) * 5,
    ))


# ---------------------------------------------------------------------------
# 2-D pair-space sharding (production TPU mesh)
# ---------------------------------------------------------------------------

def _local_pair_scores(vr, vc, acc_r, acc_c, p_hat, s, n, has_pod):
    """Per-device: C_same→ tile + shared-count tile over the local entry shard.

    vr: (S_r, K, w) row-block incidence (entry shard local)
    vc: (S_c, K, w) column-block incidence
    """
    f_a1 = acc_r[:, None]
    f_a2 = acc_c[None, :]

    def body(carry, xs):
        c_same, n_cnt = carry
        vr_k, vc_k, p_k = xs
        if vr_k.dtype == jnp.int8:
            # int8 incidence (§Perf H3): halves HBM traffic vs bf16; the MXU
            # accumulates 0/1 products exactly in int32
            count = jnp.dot(vr_k, vc_k.T,
                            preferred_element_type=jnp.int32).astype(jnp.float32)
        else:
            count = jnp.dot(vr_k, vc_k.T, preferred_element_type=jnp.float32)
        # p is constant within a bucket ⇒ any local representative works
        f = score_same(p_k[0], f_a1, f_a2, s, n)
        return (c_same + f * count, n_cnt + count), None

    S_r = vr.shape[0]
    S_c = vc.shape[0]
    # the accumulators are device-varying over the pair-tile axes — mark them
    varying = ("data", "model") + (("pod",) if has_pod else ())
    zero = jax.lax.pcast(jnp.zeros((S_r, S_c), jnp.float32), varying,
                         to="varying")
    (c_same, n_cnt), _ = jax.lax.scan(
        body, (zero, zero), (jnp.moveaxis(vr, 1, 0), jnp.moveaxis(vc, 1, 0), p_hat))
    if has_pod:
        c_same = jax.lax.psum(c_same, "pod")
        n_cnt = jax.lax.psum(n_cnt, "pod")
    return c_same, n_cnt


def distributed_pair_scores_lowerable(mesh: Mesh, n_sources: int, K: int,
                                      width: int, cfg: CopyConfig,
                                      dtype=jnp.bfloat16):
    """Shapes-only variant for the dry-run: returns a Lowered without ever
    materializing the (K, S, w) incidence tensor (which at 1M-source scale
    would be hundreds of GB on the host)."""
    has_pod = "pod" in mesh.axis_names
    if has_pod:
        width += (-width) % mesh.shape["pod"]
    e_axis = "pod" if has_pod else None
    spec_r = P("data", None, e_axis)
    spec_c = P("model", None, e_axis)
    out_spec = P("data", "model")
    shard_fn = jax.jit(
        shard_map(
            partial(_local_pair_scores, s=cfg.s, n=cfg.n, has_pod=has_pod),
            mesh=mesh,
            in_specs=(spec_r, spec_c, P("data"), P("model"),
                      P(None, e_axis) if has_pod else P(None, None)),
            out_specs=(out_spec, out_spec),
        ),
        in_shardings=(
            NamedSharding(mesh, spec_r), NamedSharding(mesh, spec_c),
            NamedSharding(mesh, P("data")), NamedSharding(mesh, P("model")),
            NamedSharding(mesh, P(None, e_axis) if has_pod else P(None, None)),
        ),
        out_shardings=(NamedSharding(mesh, out_spec),
                       NamedSharding(mesh, out_spec)),
    )
    v_sds = jax.ShapeDtypeStruct((n_sources, K, width), dtype)
    acc_sds = jax.ShapeDtypeStruct((n_sources,), jnp.float32)
    p_sds = jax.ShapeDtypeStruct((K, width), jnp.float32)
    return shard_fn.lower(v_sds, v_sds, acc_sds, acc_sds, p_sds)


def distributed_pair_scores(
    mesh: Mesh,
    v_ksw: np.ndarray,          # (K, S, w) bucketed incidence (bf16/f32)
    p_hat: np.ndarray,          # (K,)
    acc: np.ndarray,            # (S,)
    cfg: CopyConfig,
):
    """Lowerable distributed C_same→/count computation.

    Returns a jitted function-of-nothing whose output shardings tile C over
    (data, model); call ``.lower().compile()`` for the dry-run or execute on
    a real mesh. Entry (bucket-width) dim is sharded over 'pod' when present.
    """
    has_pod = "pod" in mesh.axis_names
    K, S, w = v_ksw.shape

    # pad the entry width to a multiple of the pod axis (zero columns are
    # inert: they contribute 0 to every co-occurrence count)
    if has_pod:
        pods = mesh.shape["pod"]
        w_pad = (-w) % pods
        if w_pad:
            v_ksw = np.pad(np.asarray(v_ksw), ((0, 0), (0, 0), (0, w_pad)))
            w += w_pad

    # (S, K, w) layouts so the S dim is leading for row/col sharding
    v_skw = jnp.asarray(np.moveaxis(np.asarray(v_ksw), 0, 1))
    acc = jnp.asarray(acc, jnp.float32)
    p_hat_a = jnp.asarray(p_hat, jnp.float32)

    e_axis = "pod" if has_pod else None
    spec_r = P("data", None, e_axis)
    spec_c = P("model", None, e_axis)
    out_spec = P("data", "model")

    shard_fn = jax.jit(
        shard_map(
            partial(_local_pair_scores, s=cfg.s, n=cfg.n, has_pod=has_pod),
            mesh=mesh,
            in_specs=(spec_r, spec_c, P("data"), P("model"),
                      P(None, e_axis) if has_pod else P(None, None)),
            out_specs=(out_spec, out_spec),
        ),
        in_shardings=(
            NamedSharding(mesh, spec_r), NamedSharding(mesh, spec_c),
            NamedSharding(mesh, P("data")), NamedSharding(mesh, P("model")),
            NamedSharding(mesh, P(None, e_axis) if has_pod else P(None, None)),
        ),
        out_shardings=(NamedSharding(mesh, out_spec), NamedSharding(mesh, out_spec)),
    )

    # p_hat must broadcast per (K, w_local) — expand to (K, w) so the entry
    # shard picks the right representative for its slice
    p_kw = jnp.broadcast_to(p_hat_a[:, None], (K, w))

    def run():
        """Execute the sharded pass and return (C_same→, count) tiles."""
        return shard_fn(v_skw, v_skw, acc, acc, p_kw)

    def lower():
        """Lower (without executing) for the compile-only dry-run path."""
        args = (
            jax.ShapeDtypeStruct(v_skw.shape, v_skw.dtype),
            jax.ShapeDtypeStruct(v_skw.shape, v_skw.dtype),
            jax.ShapeDtypeStruct(acc.shape, acc.dtype),
            jax.ShapeDtypeStruct(acc.shape, acc.dtype),
            jax.ShapeDtypeStruct((K, w), jnp.float32),
        )
        return shard_fn.lower(*args)

    run.lower = lower
    return run
