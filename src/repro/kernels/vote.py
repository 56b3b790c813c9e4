"""Pallas TPU kernel for the copy-discounted vote of truth finding.

One round of ACCU voting (``core/truthfind.py``) needs, for every value
group e,

    votes[e] = Σ_s V[s,e] · σ_s · exp(logI[s,e]),   logI = (L ⊙ H) @ V

with V the (S, E) 0/1 incidence of sources on value groups. ``logI`` has
one number per source and group, S·E of them (1.73 G at Book-full), though
only the claims (V[s,e] = 1) are read. The kernel never writes it out: a
grid step accumulates one (block_s, block_e) tile of ``logI`` in VMEM over
the source (contraction) blocks, and the last step folds the tile into the
block's votes — V times σ times exp, summed over the tile's rows — so HBM
holds V (int8) and the votes only.

Precision. ``L ⊙ H`` is float32, V is exactly 0/1. The MXU multiplies
bfloat16, so the left operand comes as three bfloat16 parts, hi + mid +
lo (``split3``), whose sum carries float32's 24 significant bits; each
part's product with a 0/1 V is exact and accumulates in float32. The
result equals a ``precision="highest"`` float32 product up to float32
rounding of the sums.

Grid: (E/be, S/bs, S/bs) — value-group blocks outermost (parallel), then
the output row block, then the contraction block innermost; the (1, be)
vote block is revisited across both inner axes.

VMEM per step (bs = 640, be = 512, int8 V): the three LH parts
3·640·640·2 B = 2.4 MiB, the two V tiles 2·640·512 B = 640 KiB, each
double-buffered, plus the (640, 512) float32 ``logI`` tile 1.25 MiB and
the matmul temporaries: about 10 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Scoped VMEM the kernel may use (bytes); v5e has 128 MiB of VMEM and
#: grants 16 MiB by default, about what the default blocks need.
VMEM_LIMIT = 48 * 1024 * 1024


def split3(x: jnp.ndarray) -> jnp.ndarray:
    """(3, ...) bfloat16 parts hi, mid, lo of a float32 array, with
    hi + mid + lo equal to ``x`` up to float32 rounding."""
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.stack([hi, mid, lo])


def _vote_kernel(lh_ref, vk_ref, vi_ref, sig_ref, o_ref, acc_ref, *,
                 n_k: int):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init_votes():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(k == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = vk_ref[...].astype(jnp.float32).astype(jnp.bfloat16)   # exact 0/1
    acc = acc_ref[...]
    for part in (2, 1, 0):                       # lo, mid, hi: small first
        acc += jnp.dot(lh_ref[part], v, preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _fold():
        w = vi_ref[...].astype(jnp.float32) * sig_ref[...] * jnp.exp(acc)
        o_ref[...] += jnp.sum(w, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_e", "interpret"))
def vote_fused_pallas(lh3, v, sigma, *, block_s: int = 640,
                      block_e: int = 512, interpret: bool = False):
    """Votes of every value group: ``(1, E)`` float32.

    Args:
      lh3: (3, S, S) bfloat16, ``split3(L ⊙ H)``; S % block_s == 0.
      v: (S, E) int8 0/1 incidence; E % block_e == 0. Padding rows and
        columns are zero.
      sigma: (S, 1) float32 vote weights σ_s (0 on padding rows).
    """
    _, S, S2 = lh3.shape
    assert S == S2 == v.shape[0] == sigma.shape[0], (lh3.shape, v.shape)
    E = v.shape[1]
    assert S % block_s == 0 and E % block_e == 0, (S, E, block_s, block_e)
    n_k = S // block_s
    grid = (E // block_e, n_k, n_k)
    in_specs = [
        pl.BlockSpec((3, block_s, block_s), lambda j, i, k: (0, i, k)),
        pl.BlockSpec((block_s, block_e), lambda j, i, k: (k, j)),  # matmul
        pl.BlockSpec((block_s, block_e), lambda j, i, k: (i, j)),  # claims
        pl.BlockSpec((block_s, 1), lambda j, i, k: (i, 0)),
    ]
    out_spec = pl.BlockSpec((1, block_e), lambda j, i, k: (0, j))
    interp = pltpu.InterpretParams() if interpret else False
    return pl.pallas_call(
        functools.partial(_vote_kernel, n_k=n_k),
        grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((1, E), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_s, block_e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interp, name="vote_fused_pallas",
    )(lh3, v, v, sigma.astype(jnp.float32))


__all__ = ["VMEM_LIMIT", "split3", "vote_fused_pallas"]
