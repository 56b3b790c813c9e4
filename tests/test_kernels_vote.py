"""The copy-discounted vote: ``vote_fused_pallas`` (interpret mode) and the
program's round (``truthfind.vote_round``) against the plain dense round
(``vote_round_reference``, float32 at the highest matmul precision) and
against the benchmark's independent float64 vote.

Tolerances. V is exactly 0/1 and the three bfloat16 parts carry the
float32 weights whole, so the kernel's log-independence equals the
reference's product up to float32 rounding of the sums (a few ulps of the
largest term); P(e) and the accuracies, which lie in [0, 1], then agree to
``ATOL`` absolute. A vote from one bfloat16 pass of the weights, as a
default-precision matmul gives on a TPU, errs by hundreds of times more,
and the last test shows the tolerance catches it.
"""
import importlib.util
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import truthfind as tf
from repro.core.types import CopyConfig
from repro.data.claims import SyntheticSpec, synthetic_claims
from repro.kernels.ref import vote_fused_ref
from repro.kernels.vote import split3, vote_fused_pallas

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0, c=0.8)
#: Absolute tolerance on P(e) and accuracies, both in [0, 1]: float32
#: rounding of sums of up to a few hundred terms.
ATOL = 2e-6


def _bench_reference():
    path = Path(__file__).resolve().parents[1] / "bench" / "truth_reference.py"
    spec = importlib.util.spec_from_file_location("truth_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def world():
    sc = synthetic_claims(SyntheticSpec(n_sources=60, n_items=400,
                                        coverage="book", n_cliques=4, seed=3))
    ds = sc.dataset
    S = ds.n_sources
    rng = np.random.default_rng(0)
    acc = rng.uniform(0.3, 0.95, S).astype(np.float32)
    pr_copy = (rng.uniform(0, 1, (S, S)) ** 4).astype(np.float32)
    groups = tf.build_value_groups(ds)
    ref = tf.vote_round_reference(
        jnp.asarray(groups.V_all), jnp.asarray(groups.entry_item),
        jnp.asarray(acc), jnp.asarray(pr_copy), CFG.n, CFG.c, ds.n_items,
        jnp.asarray(groups.n_values_per_item))
    return ds, groups, acc, pr_copy, [np.asarray(x) for x in ref]


def test_split3_carries_float32():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 64)) * 7,
                    jnp.float32)
    parts = split3(x).astype(jnp.float32)
    back = parts[0] + parts[1] + parts[2]
    np.testing.assert_allclose(back, x, rtol=2 ** -23, atol=0)


@pytest.mark.parametrize("block_s", [128, 256])
def test_kernel_equals_its_oracle(block_s):
    """Interpret-mode kernel against the blocked jnp oracle: the same
    products, summed in the same order within a block."""
    rng = np.random.default_rng(2)
    S, E = 256, 1024
    lh = -rng.uniform(0, 3, (S, S)).astype(np.float32)
    v = (rng.random((S, E)) < 0.1).astype(np.int8)
    sigma = rng.uniform(-1, 5, (S, 1)).astype(np.float32)
    lh3 = split3(jnp.asarray(lh))
    got = vote_fused_pallas(lh3, jnp.asarray(v), jnp.asarray(sigma),
                            block_s=block_s, block_e=512, interpret=True)
    want = vote_fused_ref(lh3, jnp.asarray(v), jnp.asarray(sigma))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # float64 truth; float32 accumulation of 3·S products errs by at most
    # 3·S·2⁻²⁴ times the sum of their magnitudes, to first order
    vf, lh64 = v.astype(np.float64), lh.astype(np.float64)
    terms = vf * np.abs(sigma) * np.exp(lh64 @ vf)
    exact = (vf * sigma * np.exp(lh64 @ vf)).sum(axis=0)
    bound = (terms * 3 * S * 2.0 ** -24 * (np.abs(lh64) @ vf)).sum(axis=0)
    assert (np.abs(np.asarray(got)[0] - exact) <= bound).all()


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_vote_round_matches_the_references(world, impl):
    ds, groups, acc, pr_copy, (ref_p, ref_acc) = world
    res = tf.upload_groups(groups)
    p, a = tf.vote_round(res, acc, pr_copy, CFG, impl=impl)
    np.testing.assert_allclose(p, ref_p, rtol=0, atol=ATOL)
    np.testing.assert_allclose(a, ref_acc, rtol=0, atol=ATOL)
    # and the benchmark's float64 vote, which shares no code with either
    rows, cols = np.nonzero(ds.values >= 0)
    b_p, b_acc = _bench_reference().vote(ds.values, acc, pr_copy,
                                         {"n": CFG.n, "c": CFG.c})
    np.testing.assert_allclose(p[groups.claim_entry[rows, cols]], b_p,
                               rtol=0, atol=ATOL)
    np.testing.assert_allclose(a, b_acc, rtol=0, atol=ATOL)


def test_one_bfloat16_pass_is_caught(world):
    """A vote whose weights pass through bfloat16 once (a default-precision
    matmul) misses the tolerance: the test can fail."""
    ds, groups, acc, pr_copy, (ref_p, _) = world
    res = tf.upload_groups(groups)
    sigma, lh = tf.vote_weights(acc, pr_copy, CFG.c, CFG.n)
    s_pad = res.v.shape[0]
    S = ds.n_sources
    lh1 = jnp.zeros((3, s_pad, s_pad), jnp.bfloat16).at[0, :S, :S].set(
        jnp.asarray(lh, jnp.bfloat16))
    sig = jnp.pad(jnp.asarray(sigma), (0, s_pad - S)).reshape(s_pad, 1)
    votes = vote_fused_ref(lh1, res.v, sig)[0, : groups.V_all.shape[1]]
    p1 = np.asarray(tf._normalize(votes, res.entry_item, res.n_vals, CFG.n,
                                  ds.n_items))
    assert np.abs(p1 - ref_p).max() > 10 * ATOL


def test_no_float32_sources_by_groups_array(world):
    """The program's round holds no (S, E) float32 array, where the dense
    reference does."""
    ds, groups, acc, pr_copy, _ = world
    res = tf.upload_groups(groups)
    sigma, lh = tf.vote_weights(acc, pr_copy, CFG.c, CFG.n)
    hlo = tf._vote_device.lower(
        res.v, res.entry_item, res.n_vals, res.claim_src, res.claim_ent,
        res.n_claims, sigma, lh, n=CFG.n, impl="ref").as_text()
    s_pad, e_pad = res.v.shape
    assert f"tensor<{s_pad}x{e_pad}xf32>" not in hlo
    S, E = groups.V_all.shape
    dense = tf.vote_round_reference.lower(
        jnp.asarray(groups.V_all), jnp.asarray(groups.entry_item),
        jnp.asarray(acc), jnp.asarray(pr_copy), CFG.n, CFG.c, ds.n_items,
        jnp.asarray(groups.n_values_per_item)).as_text()
    assert f"tensor<{S}x{E}xf32>" in dense
