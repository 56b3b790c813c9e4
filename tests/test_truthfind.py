"""Iterative fusion on the motivating example — Table II behaviour."""
import time

import numpy as np
import pytest

from repro.core.truthfind import build_value_groups, fusion_accuracy, truth_finding
from repro.core.types import CopyConfig
from repro.data.claims import (
    GROUND_TRUTH_COPIES,
    SyntheticSpec,
    motivating_example,
    synthetic_claims,
)

CFG = CopyConfig(alpha=0.1, s=0.8, n=50.0, c=0.8)


@pytest.fixture(scope="module")
def fused():
    ds = motivating_example()
    return ds, truth_finding(ds, CFG, detector="pairwise", max_rounds=8,
                             track_history=True)


def entry_prob(ds, res, item, vname):
    inv = {v: k for k, v in ds.value_names.items()}
    d, vid = inv[f"{ds.item_names.index(item) and ''}{item}.{vname}"] if False else inv[f"{item}.{vname}"]
    groups = res.groups
    # find the entry for (d, vid) via a provider
    for e in range(len(res.p_entry)):
        if groups.entry_item[e] != d:
            continue
        provs = np.nonzero(groups.V_all[:, e])[0]
        if provs.size and ds.values[provs[0], d] == vid:
            return float(res.p_entry[e])
    raise KeyError((item, vname))


def test_converges_quickly(fused):
    ds, res = fused
    # the paper's example converges in 5 rounds; allow a little slack
    assert res.rounds <= 8


def test_albany_flip(fused):
    """The signature event (Table II-b): naive voting initially prefers
    NY.NewYork (3 copier votes); copy detection flips truth to NY.Albany."""
    ds, res = fused
    assert entry_prob(ds, res, "NY", "Albany") > 0.6
    assert entry_prob(ds, res, "NY", "NewYork") < 0.3


def test_converged_value_probabilities(fused):
    ds, res = fused
    assert entry_prob(ds, res, "NJ", "Trenton") > 0.85
    assert entry_prob(ds, res, "NJ", "Atlantic") < 0.15
    assert entry_prob(ds, res, "TX", "Austin") > 0.85
    assert entry_prob(ds, res, "AZ", "Phoenix") > 0.85


def test_converged_accuracies_match_table_ii(fused):
    ds, res = fused
    acc = res.accuracy
    # Table II-a round 5: S0=.99 S1=.99 S2=.2 S3=.2 S4=.4
    assert acc[0] > 0.9 and acc[1] > 0.9
    assert acc[2] < 0.4 and acc[3] < 0.4
    assert 0.2 < acc[4] < 0.65
    # accurate independents end much higher than the copier clique
    assert acc[0] - acc[2] > 0.4


def test_copying_detected_after_convergence(fused):
    ds, res = fused
    assert GROUND_TRUTH_COPIES <= res.detection.copying_pairs()


def test_value_groups_structure():
    ds = motivating_example()
    g = build_value_groups(ds)
    # 13 shared + 3 singleton values = 16 distinct claims
    assert g.V_all.shape[1] == 16
    # every provided claim maps to an entry
    assert (g.claim_entry[ds.values >= 0] >= 0).all()
    assert (g.claim_entry[ds.values < 0] == -1).all()


def test_fusion_beats_naive_voting_on_synthetic():
    """Copy-aware fusion should recover truth better than copy-blind fusion
    when copier cliques outvote honest sources."""
    spec = SyntheticSpec(n_sources=40, n_items=300, coverage="stock",
                        n_cliques=6, clique_size=4, acc_low=0.25,
                        acc_high=0.9, seed=5)
    sc = synthetic_claims(spec)

    res_copy = truth_finding(sc.dataset, CFG, detector="index", max_rounds=6)
    acc_with = fusion_accuracy(res_copy, sc.dataset, sc.true_values)

    blind = CopyConfig(alpha=1e-9, s=CFG.s, n=CFG.n, c=0.0)  # discount disabled
    res_blind = truth_finding(sc.dataset, blind, detector="index", max_rounds=6)
    acc_without = fusion_accuracy(res_blind, sc.dataset, sc.true_values)

    assert acc_with >= acc_without
    assert acc_with > 0.8


def _value_groups_dense(ds):
    """The former grouping, keyed over the whole (S, D) grid: the reference
    ``build_value_groups`` must equal."""
    values = ds.values
    S, D = values.shape
    prov = values >= 0
    max_v = int(values.max()) + 1 if prov.any() else 1
    key = np.where(prov, np.arange(D, dtype=np.int64)[None, :] * max_v
                   + values, -1)
    uniq, inv = np.unique(key, return_inverse=True)
    inv = inv.reshape(S, D)
    offset = 1 if uniq[0] == -1 else 0
    claim_entry = np.where(prov, inv - offset, -1).astype(np.int32)
    V_all = np.zeros((S, len(uniq) - offset), dtype=np.uint8)
    rows, cols = np.nonzero(prov)
    V_all[rows, claim_entry[rows, cols]] = 1
    entry_item = (uniq[offset:] // max_v).astype(np.int32)
    n_vals = np.bincount(entry_item, minlength=D).astype(np.int32)
    return V_all, entry_item, claim_entry, n_vals


@pytest.mark.parametrize("coverage,seed", [("book", 0), ("stock", 1),
                                           ("motivating", 0)])
def test_value_groups_equal_the_dense_grouping(coverage, seed):
    ds = motivating_example() if coverage == "motivating" else \
        synthetic_claims(SyntheticSpec(n_sources=60, n_items=400,
                                       coverage=coverage, n_cliques=4,
                                       seed=seed)).dataset
    g = build_value_groups(ds)
    want = _value_groups_dense(ds)
    got = (g.V_all, g.entry_item, g.claim_entry, g.n_values_per_item)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@pytest.fixture(scope="module")
def rounds():
    """Five fixed rounds of HYBRID, the INCREMENTAL bootstrap and three
    INCREMENTAL rounds, with the spans and counters they left."""
    from repro.utils import trace

    sc = synthetic_claims(SyntheticSpec(n_sources=60, n_items=400,
                                        coverage="book", n_cliques=4,
                                        seed=3))
    t0 = time.perf_counter()
    res = truth_finding(sc.dataset, CFG, detector="incremental", tol=0.0,
                        max_rounds=5, track_history=True)
    return sc.dataset, res, trace.records(t0)


def test_every_round_decides_as_exact_index(rounds):
    """Each round's decisions equal exact INDEX given that round's own
    inputs: the accuracies and claim probabilities of the round before."""
    from repro.core.bucketed import index_detect_exact
    from repro.core.types import ClaimsDataset

    ds, res, _ = rounds
    assert res.rounds == 5 and len(res.detections) == 5
    assert len(res.accuracy_history) == len(res.p_history) == 6
    ce = res.groups.claim_entry
    for r, det in enumerate(res.detections, start=1):
        acc = res.accuracy_history[r - 1]
        p_claim = np.where(ce >= 0, res.p_history[r - 1][np.maximum(ce, 0)],
                           0.0).astype(np.float32)
        want = index_detect_exact(ClaimsDataset(values=ds.values,
                                                accuracy=acc), p_claim, CFG)
        np.testing.assert_array_equal(det.copying, want.copying, err_msg=r)
    np.testing.assert_array_equal(res.accuracy, res.accuracy_history[-1])


def test_a_run_leaves_its_span_tree(rounds):
    ds, res, recs = rounds
    spans = {r.id: r for r in recs if r.value is None}
    run = [r for r in spans.values() if r.name == "truth.run"]
    assert len(run) == 1 and run[0].parent is None

    def under(name):
        return [r for r in recs if r.name == name]

    def parent(r):
        return spans[r.parent].name

    assert [parent(r) for r in under("truth.value_groups")] == ["truth.run"]
    votes = sorted(under("truth.vote"), key=lambda r: r.t0)
    assert [r.attrs["round"] for r in votes] == list(range(6))
    assert {parent(r) for r in votes} == {"truth.run"}
    detects = sorted(under("engine.detect"), key=lambda r: r.t0)
    assert [r.attrs["mode"] for r in detects] == ["hybrid"] + \
        ["incremental"] * 4
    assert [parent(r) for r in under("engine.bound")] == ["engine.detect"] * 2
    assert [parent(r) for r in under("engine.incremental")] == \
        ["engine.detect"] * 3
    assert {parent(r) for r in under("incremental.pass1")} == \
        {"engine.incremental"}
    assert {parent(r) for r in under("incremental.masked_counts")} == \
        {"incremental.pass1"}
    assert {parent(r) for r in under("engine.rescore")} <= \
        {"engine.bound", "engine.incremental"}
    for name in ("incremental.candidates", "incremental.big_entries",
                 "incremental.masked_entries"):
        got = under(name)
        assert len(got) == 3 and {parent(r) for r in got} == \
            {"incremental.pass1"}, name
    assert [r.value for r in under("incremental.candidates")] == \
        [c.pairs_considered for c in res.counters[2:]]
    (n_rounds,) = under("truth.rounds")
    assert n_rounds.value == 5 and parent(n_rounds) == "truth.run"
    h2d = under("truth.h2d_bytes")
    S = ds.n_sources
    assert len(h2d) == 7                        # the groups, then 6 votes
    assert parent(h2d[0]) == "truth.value_groups"
    assert h2d[0].value >= res.groups.V_all.nbytes
    assert [r.value for r in h2d[1:]] == [S * S * 4 + S * 4] * 6
