"""Pure-jnp oracles for every Pallas kernel in this package.

Each function has identical semantics (including block-constant
approximations) to its kernel so tests can assert allclose.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.vma import zeros_varying_like


# ---------------------------------------------------------------------------
# copyscore
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("s", "n_false", "block_e"))
def copyscore_ref(v, p_blk, acc, *, s, n_false, block_e=512,
                  v_cols=None, acc_cols=None, delta_blk=None):
    """Block-constant-p copy-score accumulation; oracle for copyscore_pallas.

    Like the kernel, ``v_cols``/``acc_cols`` select a rectangular pair tile
    (rows copy from columns); omitted, it computes the full square S×S.
    ``delta_blk`` adds the error-bound channel err = Σ δ_blk·count.
    """
    vj = v if v_cols is None else v_cols
    accj = acc if acc_cols is None else acc_cols
    S_i, E = v.shape
    S_j = vj.shape[0]
    n_e = E // block_e
    vi_f = v.astype(jnp.float32).reshape(S_i, n_e, block_e)
    vj_f = vj.astype(jnp.float32).reshape(S_j, n_e, block_e)
    a1 = acc.astype(jnp.float32)[:, None]
    a2 = accj.astype(jnp.float32)[None, :]
    with_err = delta_blk is not None
    d_blk = (delta_blk if with_err else jnp.zeros(n_e)).astype(jnp.float32)

    def body(carry, xs):
        c, n, err = carry
        vi_k, vj_k, p_k, d_k = xs                      # (S_i, be), (S_j, be), scalars
        count = jnp.dot(vi_k, vj_k.T, preferred_element_type=jnp.float32)
        pr_src = p_k * a2 + (1.0 - p_k) * (1.0 - a2)
        pr_ind = p_k * a1 * a2 + (1.0 - p_k) * (1.0 - a1) * (1.0 - a2) / n_false
        f = jnp.log(1.0 - s + s * pr_src / pr_ind)
        return (c + f * count, n + count, err + d_k * count), None

    zero = zeros_varying_like((S_i, S_j), v, vj, acc, accj, p_blk, d_blk)
    (c, n, err), _ = jax.lax.scan(body, (zero, zero, zero),
                                  (jnp.moveaxis(vi_f, 1, 0),
                                   jnp.moveaxis(vj_f, 1, 0),
                                   p_blk.astype(jnp.float32), d_blk))
    if with_err:
        return c, n, err
    return c, n


@partial(jax.jit, static_argnames=("s", "n_false", "block_e"))
def copyscore_fused_ref(v, p_blk, acc, *, s, n_false, block_e=512,
                        v_cols=None, acc_cols=None, delta_blk=None,
                        nout_blk=None):
    """Dual-direction oracle for ``copyscore_fused_pallas``.

    Returns (C_same→, C_same←, n, n_out, err), all (S_i, S_j) f32, from one
    shared count per entry block. C_same←[i,j] scores column j copying from
    row i — only the copied-source accuracy role swaps in f; its transpose is
    the mirrored tile's C_same→. ``nout_blk`` (default all-ones) masks which
    blocks count toward n_out; ``delta_blk`` (default zero) feeds err.
    """
    vj = v if v_cols is None else v_cols
    accj = acc if acc_cols is None else acc_cols
    S_i, E = v.shape
    S_j = vj.shape[0]
    n_e = E // block_e
    vi_f = v.astype(jnp.float32).reshape(S_i, n_e, block_e)
    vj_f = vj.astype(jnp.float32).reshape(S_j, n_e, block_e)
    a1 = acc.astype(jnp.float32)[:, None]
    a2 = accj.astype(jnp.float32)[None, :]
    d_blk = (jnp.zeros(n_e) if delta_blk is None else delta_blk).astype(jnp.float32)
    m_blk = (jnp.ones(n_e) if nout_blk is None else nout_blk).astype(jnp.float32)

    def body(carry, xs):
        cf, cb, n, n_out, err = carry
        vi_k, vj_k, p_k, d_k, m_k = xs
        count = jnp.dot(vi_k, vj_k.T, preferred_element_type=jnp.float32)
        # symmetric association (a1·a2 first): bitwise invariant under a1↔a2,
        # matching the kernel — on a diagonal tile C← == C→ᵀ exactly
        pr_ind = p_k * (a1 * a2) + (1.0 - p_k) * ((1.0 - a1) * (1.0 - a2)) / n_false
        f_fwd = jnp.log(1.0 - s + s * (p_k * a2 + (1.0 - p_k) * (1.0 - a2)) / pr_ind)
        f_bwd = jnp.log(1.0 - s + s * (p_k * a1 + (1.0 - p_k) * (1.0 - a1)) / pr_ind)
        return (cf + f_fwd * count, cb + f_bwd * count, n + count,
                n_out + m_k * count, err + d_k * count), None

    zero = zeros_varying_like((S_i, S_j), v, vj, acc, accj, p_blk, d_blk,
                              m_blk)
    carry, _ = jax.lax.scan(body, (zero,) * 5,
                            (jnp.moveaxis(vi_f, 1, 0), jnp.moveaxis(vj_f, 1, 0),
                             p_blk.astype(jnp.float32), d_blk, m_blk))
    return carry


# ---------------------------------------------------------------------------
# vote
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_e",))
def vote_fused_ref(lh3, v, sigma, *, block_e=512):
    """Oracle for ``vote_fused_pallas``: the same three-part bfloat16
    product and fold, one block of ``block_e`` value groups at a time, so
    no (S, E) float32 array is made here either."""
    S, E = v.shape
    lh3 = lh3.astype(jnp.bfloat16)
    sigma = sigma.astype(jnp.float32).reshape(S, 1)

    def block(j):
        vj = jax.lax.dynamic_slice_in_dim(v, j * block_e, block_e, axis=1)
        x = vj.astype(jnp.bfloat16)
        log_i = jnp.zeros((S, block_e), jnp.float32)
        for part in (2, 1, 0):
            log_i += jnp.dot(lh3[part], x, preferred_element_type=jnp.float32)
        return jnp.sum(vj.astype(jnp.float32) * sigma * jnp.exp(log_i), axis=0)

    return jax.lax.map(block, jnp.arange(E // block_e)).reshape(1, E)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def attention_chunked(q, k, v, *, causal=True, sm_scale=None, window=None,
                      chunk=2048, unroll=False):
    """Flash-style attention in pure XLA: scan over q chunks so peak memory
    is O(chunk·S) instead of O(S²). Numerically ≡ attention_ref. ``unroll``
    inlines the chunk loop (used by the dry-run probes so cost_analysis
    counts every chunk — XLA tallies a while body once).

    Memory design (EXPERIMENTS.md §Perf H1): kv heads are never repeated to
    q heads (grouped einsum over the GQA group dim), k/v stay in their input
    dtype with f32 accumulation, and sliding-window layers slice only the
    window+chunk keys each q chunk can see instead of all S of them.
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    Sk = k.shape[2]
    n_chunks = Sq // chunk
    assert Sq % chunk == 0, (Sq, chunk)
    qg = q.reshape(B, Hkv, group, Sq, D)
    qc = jnp.moveaxis(qg.reshape(B, Hkv, group, n_chunks, chunk, D), 3, 0)
    kwin = min(window + chunk, Sk) if window is not None else Sk

    def one_chunk(_, qi_pair):
        qi, ci = qi_pair                                   # (B,Hkv,g,chunk,D)
        q_pos = ci * chunk + jnp.arange(chunk)[:, None]
        if window is not None:
            start = jnp.clip(ci * chunk + chunk - kwin, 0, Sk - kwin)
            ks = jax.lax.dynamic_slice_in_dim(k, start, kwin, axis=2)
            vs = jax.lax.dynamic_slice_in_dim(v, start, kwin, axis=2)
            k_pos = start + jnp.arange(kwin)[None, :]
        else:
            ks, vs = k, v
            k_pos = jnp.arange(Sk)[None, :]
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qi, ks,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones(q_pos.shape[:1] + k_pos.shape[1:], bool)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vs.astype(jnp.float32))
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(one_chunk, None,
                           (qc, jnp.arange(n_chunks)),
                           unroll=n_chunks if unroll else 1)
    # (n_chunks, B, Hkv, g, chunk, D) → (B, Hq, Sq, D)
    outs = jnp.moveaxis(outs, 0, 3)
    return outs.reshape(B, Hq, Sq, D)


def attention_ref(q, k, v, *, causal=True, sm_scale=None, window=None):
    """Reference attention. q (B,Hq,S,D); k,v (B,Hkv,S,D) with Hq % Hkv == 0.

    window (int): sliding-window size — key j visible from query i iff
    0 ≤ i − j < window (combined with causal).
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, group, Sq, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    Sk = k.shape[2]
    qi = jnp.arange(Sq)[:, None]
    kj = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qi >= kj
    if window is not None:
        mask &= (qi - kj) < window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)
