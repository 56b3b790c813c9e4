"""Pallas TPU kernels for the bucketed copy-score accumulation (DESIGN.md §2.1).

The hot loop of scalable copy detection is

    C_same→[i,j] = Σ_e V[i,e]·V[j,e]·f→(A_i, A_j, p_e)
    n[i,j]       = Σ_e V[i,e]·V[j,e]

with entries pre-sorted so that every contiguous block of ``block_e`` entries
shares one representative probability p̂ (bucket-aligned padding done by
``ops.copyscore``). Within a block the pair score f→ is constant per (i,j),
so each grid step is ONE (block_i × block_e) @ (block_e × block_j) MXU matmul
plus one VPU elementwise combine — arithmetic intensity ≈ block_e FLOPs/byte
on the C tiles instead of the O(1) a naive gather implementation would get.

Two kernel families:

``copyscore_pallas``        — single-direction (C_same→, n[, err]); kept for
                              the full-square ``ops.copyscore`` wrapper and as
                              the legacy baseline the kernel microbenchmark
                              compares against.
``copyscore_fused_pallas``  — the production dual-direction kernel (DESIGN.md
                              §3). Copy detection is symmetric at heart: every
                              unordered pair needs both C→ and C← before a
                              decision, and the count matmul is shared. One
                              matmul per entry block feeds FIVE accumulators —
                              C_same→, C_same← (f→/f← only swap the a1/a2
                              roles in the VPU combine), the shared count, the
                              non-Ē count (a per-block 0/1 mask channel that
                              replaces the separate full-incidence matmul the
                              tiled path used to do), and the p̂-error bound.
                              int8 incidence takes the exact int32 MXU
                              accumulation path (counts are ≤ block_e ≪ 2³¹),
                              halving HBM traffic vs bf16.

Grid: (S/bi, S/bj, E/be) with the entry dimension innermost so the output
tiles live in VMEM across the whole reduction (revisited-output accumulation).

VMEM budget per step (defaults bi=bj=128, be=512, int8 V, fused):
  V_i, V_j tiles:    2 · 128·512·1 B = 128 KiB
  5 accum tiles:     5 · 128·128·4 B = 320 KiB
  A_i, A_j, scalars: ~1 KiB                        → ≈ 0.45 MiB ≪ 16 MiB VMEM.
MXU work per step: 128·512·128 MACs with both matmul dims multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vma import vary_all


def _interpret_params(interpret: bool):
    """``interpret=True`` runs the TPU interpreter, which simulates SMEM and
    VMEM and, unlike the generic one, evaluates inside ``shard_map``."""
    return pltpu.InterpretParams() if interpret else False


#: Per-entry-block scalars (p̂, δ, non-Ē flag) live whole in SMEM and are
#: read at ``pl.program_id(2)``: a (1, 1) VMEM block of an (n_e, 1) array
#: is not (8, 128)-tiled, which Mosaic refuses once n_e > 1.
_BLOCK_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)


def _count_matmul(vi, vj):
    """The shared count matmul. int8 incidence accumulates exactly on the MXU
    in int32 (0/1 products, partial sums ≤ block_e); floats accumulate in f32."""
    if vi.dtype == jnp.int8:
        return jax.lax.dot_general(
            vi, vj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    return jax.lax.dot_general(
        vi, vj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _copyscore_kernel(p_ref, vi_ref, vj_ref, ai_ref, aj_ref,
                      c_ref, n_ref, *, s: float, n_false: float):
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    count = _count_matmul(vi_ref[...], vj_ref[...])    # (bi, bj) on the MXU

    p = p_ref[e]
    a1 = ai_ref[...].astype(jnp.float32)               # (bi, 1) copier accuracy
    a2 = aj_ref[...].astype(jnp.float32).reshape(1, -1)  # (1, bj) source accuracy
    pr_src = p * a2 + (1.0 - p) * (1.0 - a2)
    pr_ind = p * a1 * a2 + (1.0 - p) * (1.0 - a1) * (1.0 - a2) / n_false
    f = jnp.log(1.0 - s + s * pr_src / pr_ind)         # Eq. (6), per pair

    c_ref[...] += f * count
    n_ref[...] += count


def _copyscore_err_kernel(p_ref, d_ref, vi_ref, vj_ref, ai_ref, aj_ref,
                          c_ref, n_ref, err_ref, *, s: float, n_false: float):
    """copyscore + an error-bound channel: err += δ_block · count, where
    δ_block bounds |f(·,·,p) − f(·,·,p̂)| over the block's true p range. The
    engine exactly rescores every pair whose decision margin is inside its
    accumulated bound, keeping binary decisions equal to the exact INDEX."""
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        err_ref[...] = jnp.zeros_like(err_ref)

    count = _count_matmul(vi_ref[...], vj_ref[...])

    p = p_ref[e]
    a1 = ai_ref[...].astype(jnp.float32)
    a2 = aj_ref[...].astype(jnp.float32).reshape(1, -1)
    pr_src = p * a2 + (1.0 - p) * (1.0 - a2)
    pr_ind = p * a1 * a2 + (1.0 - p) * (1.0 - a1) * (1.0 - a2) / n_false
    f = jnp.log(1.0 - s + s * pr_src / pr_ind)

    c_ref[...] += f * count
    n_ref[...] += count
    err_ref[...] += d_ref[e] * count


@functools.partial(
    jax.jit,
    static_argnames=("s", "n_false", "block_i", "block_j", "block_e", "interpret"),
)
def copyscore_pallas(
    v: jnp.ndarray,          # (S_i, E) incidence, bf16/f32; E % block_e == 0
    p_blk: jnp.ndarray,      # (E // block_e,) representative p̂ per entry block
    acc: jnp.ndarray,        # (S_i,) source accuracies, f32
    *,
    s: float,
    n_false: float,
    block_i: int = 128,
    block_j: int = 128,
    block_e: int = 512,
    interpret: bool = False,
    v_cols: jnp.ndarray | None = None,    # (S_j, E) column-block incidence
    acc_cols: jnp.ndarray | None = None,  # (S_j,)
    delta_blk: jnp.ndarray | None = None,  # (E // block_e,) error bound δ
):
    """Returns (C_same→ (S_i,S_j) f32, n (S_i,S_j) f32)[, err (S_i,S_j) f32].

    Square by default (v vs itself); passing ``v_cols``/``acc_cols`` computes
    a rectangular pair tile — rows copy from columns — which is how the
    DetectionEngine feeds one pruned tile of the S×S pair space at a time.
    With ``delta_blk``, a third output accumulates the per-pair score-error
    bound Σ δ_blk·count (the engine's exact-rescore trigger). Row/column
    counts must divide by their block sizes.
    """
    vj = v if v_cols is None else v_cols
    accj = acc if acc_cols is None else acc_cols
    S_i, E = v.shape
    S_j = vj.shape[0]
    assert S_i % block_i == 0 and S_j % block_j == 0, (S_i, S_j, block_i, block_j)
    assert E % block_e == 0, (E, block_e)
    n_e = E // block_e

    scalars = [p_blk] if delta_blk is None else [p_blk, delta_blk]
    scalars = [x.reshape(n_e).astype(jnp.float32) for x in scalars]
    a_i = acc.reshape(S_i, 1).astype(jnp.float32)
    a_j = accj.reshape(S_j, 1).astype(jnp.float32)
    (v, vj, a_i, a_j, *scalars), vma = vary_all(v, vj, a_i, a_j, *scalars)

    grid = (S_i // block_i, S_j // block_j, n_e)
    in_specs = [_BLOCK_SCALARS] * len(scalars) + [               # p̂[, δ]
        pl.BlockSpec((block_i, block_e), lambda i, j, e: (i, e)),  # V rows
        pl.BlockSpec((block_j, block_e), lambda i, j, e: (j, e)),  # V cols
        pl.BlockSpec((block_i, 1), lambda i, j, e: (i, 0)),      # A_i
        pl.BlockSpec((block_j, 1), lambda i, j, e: (j, 0)),      # A_j
    ]
    out_spec = pl.BlockSpec((block_i, block_j), lambda i, j, e: (i, j))
    out_sds = jax.ShapeDtypeStruct((S_i, S_j), jnp.float32, vma=vma)
    n_out = 1 + len(scalars)                                     # C, n[, err]
    kernel = functools.partial(
        _copyscore_kernel if delta_blk is None else _copyscore_err_kernel,
        s=float(s), n_false=float(n_false))
    return tuple(pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=[out_spec] * n_out, out_shape=[out_sds] * n_out,
        interpret=_interpret_params(interpret),
    )(*scalars, v, vj, a_i, a_j))


def _copyscore_fused_kernel(p_ref, d_ref, m_ref, vi_ref, vj_ref, ai_ref, aj_ref,
                            cf_ref, cb_ref, n_ref, o_ref, e_ref,
                            *, s: float, n_false: float):
    """Dual-direction copyscore: ONE count matmul per entry block feeds both
    tile orientations plus the count / non-Ē-count / error-bound channels.

    f→ scores rows-copy-from-columns; f← scores columns-copy-from-rows, which
    only swaps which accuracy plays the copied-source role in Pr(Φ_D(S2))
    (Pr-independent is symmetric in A1/A2). So C←[i,j] = f←·count accumulates
    the (col, row) orientation of the same tile — the engine scatters its
    transpose at the mirrored tile coordinate and never schedules (c, r).
    """
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        cf_ref[...] = jnp.zeros_like(cf_ref)
        cb_ref[...] = jnp.zeros_like(cb_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        o_ref[...] = jnp.zeros_like(o_ref)
        e_ref[...] = jnp.zeros_like(e_ref)

    count = _count_matmul(vi_ref[...], vj_ref[...])    # (bi, bj)

    p = p_ref[e]
    a1 = ai_ref[...].astype(jnp.float32)               # (bi, 1) row accuracy
    a2 = aj_ref[...].astype(jnp.float32).reshape(1, -1)  # (1, bj) col accuracy
    # pr_ind associates the accuracy products symmetrically (a1·a2 first), so
    # it is bitwise invariant under a1↔a2 — on a diagonal tile C← == C→ᵀ
    # exactly, which the engine relies on when scattering both orientations
    pr_ind = p * (a1 * a2) + (1.0 - p) * ((1.0 - a1) * (1.0 - a2)) / n_false
    f_fwd = jnp.log(1.0 - s + s * (p * a2 + (1.0 - p) * (1.0 - a2)) / pr_ind)
    f_bwd = jnp.log(1.0 - s + s * (p * a1 + (1.0 - p) * (1.0 - a1)) / pr_ind)

    cf_ref[...] += f_fwd * count
    cb_ref[...] += f_bwd * count
    n_ref[...] += count
    o_ref[...] += m_ref[e] * count                     # non-Ē blocks only
    e_ref[...] += d_ref[e] * count


@functools.partial(
    jax.jit,
    static_argnames=("s", "n_false", "block_i", "block_j", "block_e", "interpret"),
)
def copyscore_fused_pallas(
    v: jnp.ndarray,          # (S_i, E) incidence, int8/bf16/f32; E % block_e == 0
    p_blk: jnp.ndarray,      # (E // block_e,) representative p̂ per entry block
    acc: jnp.ndarray,        # (S_i,) source accuracies, f32
    *,
    s: float,
    n_false: float,
    block_i: int = 128,
    block_j: int = 128,
    block_e: int = 512,
    interpret: bool = False,
    v_cols: jnp.ndarray | None = None,    # (S_j, E) column-block incidence
    acc_cols: jnp.ndarray | None = None,  # (S_j,)
    delta_blk: jnp.ndarray | None = None,  # (E // block_e,) error bound δ
    nout_blk: jnp.ndarray | None = None,   # (E // block_e,) 1.0 ⇔ block ∉ Ē
):
    """Fused dual-direction copyscore over one (rectangular) pair tile.

    Returns five (S_i, S_j) f32 arrays: (C_same→, C_same←, n, n_out, err).
    C_same← is the columns-copy-from-rows orientation — its transpose is the
    mirrored tile's C_same→, so a triangular (r ≤ c) schedule covers the full
    pair space. ``nout_blk`` masks which entry blocks count toward n_out (the
    engine's considered test: blocks before the Ē boundary); default all.
    ``delta_blk`` defaults to zero (no error channel accumulation).
    """
    vj = v if v_cols is None else v_cols
    accj = acc if acc_cols is None else acc_cols
    S_i, E = v.shape
    S_j = vj.shape[0]
    assert S_i % block_i == 0 and S_j % block_j == 0, (S_i, S_j, block_i, block_j)
    assert E % block_e == 0, (E, block_e)
    n_e = E // block_e

    p1 = p_blk.reshape(n_e).astype(jnp.float32)
    d_blk = jnp.zeros(n_e) if delta_blk is None else delta_blk
    m_blk = jnp.ones(n_e) if nout_blk is None else nout_blk
    d1 = d_blk.reshape(n_e).astype(jnp.float32)
    m1 = m_blk.reshape(n_e).astype(jnp.float32)
    a_i = acc.reshape(S_i, 1).astype(jnp.float32)
    a_j = accj.reshape(S_j, 1).astype(jnp.float32)
    (p1, d1, m1, v, vj, a_i, a_j), vma = vary_all(p1, d1, m1, v, vj, a_i,
                                                  a_j)

    grid = (S_i // block_i, S_j // block_j, n_e)
    in_specs = [
        _BLOCK_SCALARS,                                          # p̂
        _BLOCK_SCALARS,                                          # δ
        _BLOCK_SCALARS,                                          # non-Ē mask
        pl.BlockSpec((block_i, block_e), lambda i, j, e: (i, e)),  # V rows
        pl.BlockSpec((block_j, block_e), lambda i, j, e: (j, e)),  # V cols
        pl.BlockSpec((block_i, 1), lambda i, j, e: (i, 0)),      # A_i
        pl.BlockSpec((block_j, 1), lambda i, j, e: (j, 0)),      # A_j
    ]
    out_spec = pl.BlockSpec((block_i, block_j), lambda i, j, e: (i, j))
    out_sds = jax.ShapeDtypeStruct((S_i, S_j), jnp.float32, vma=vma)

    kernel = functools.partial(_copyscore_fused_kernel, s=float(s),
                               n_false=float(n_false))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=[out_spec] * 5, out_shape=[out_sds] * 5,
        interpret=_interpret_params(interpret),
    )(p1, d1, m1, v, vj, a_i, a_j)
