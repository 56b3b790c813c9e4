"""The benchmark's own corpus and traffic generators.

``SyntheticSpec``, ``synthetic_claims``, the Book-full and Stock-2wk specs,
``oracle_claim_probs`` and ``synthetic_query_rows`` are copies of the
program's ``repro.data.claims`` as it stood when the benchmark was defined,
so that the yardstick does not move when the program changes its own
generators. ``bench/tests/test_gen.py`` checks that at seed 0 the copies
reproduce the program's arrays. One addition serves the benchmark only:
``orders`` deals a run's seed into an order of the sources and of the
items, so that every seed runs the same corpus and the same requests, and
so the same work, in another order.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class SyntheticSpec:
    n_sources: int = 200
    n_items: int = 2000
    n_false: int = 50
    coverage: str = "book"             # "book" (long-tail) | "stock" (dense)
    n_cliques: int = 10
    clique_size: int = 3
    copy_selectivity: float = 0.8
    clique_items: int | None = None
    acc_low: float = 0.35
    acc_high: float = 0.95
    seed: int = 0


@dataclass
class SyntheticClaims:
    values: np.ndarray                 # (S, D) int32, -1 = item not claimed
    accuracy: np.ndarray               # (S,) float32
    copies: set = field(default_factory=set)
    copy_edges: list = field(default_factory=list)


def _coverage(rng, spec: SyntheticSpec, size: int) -> np.ndarray:
    if spec.coverage == "book":
        return np.clip(rng.pareto(1.2, size=size) * 0.01 + 0.005, 0.003, 0.9)
    return rng.uniform(0.5, 1.0, size=size)


def synthetic_claims(spec: SyntheticSpec) -> SyntheticClaims:
    """Sources with planted accuracies, coverage and copying cliques."""
    needed = spec.n_cliques * spec.clique_size
    if needed > spec.n_sources:
        raise ValueError(f"spec needs {needed} distinct clique sources, "
                         f"n_sources={spec.n_sources}")
    rng = np.random.default_rng(spec.seed)
    S, D = spec.n_sources, spec.n_items
    acc = rng.uniform(spec.acc_low, spec.acc_high, size=S).astype(np.float32)
    cov = _coverage(rng, spec, S)

    values = -np.ones((S, D), dtype=np.int32)
    for s in range(S):
        m = rng.random(D) < cov[s]
        idx = np.nonzero(m)[0]
        correct = rng.random(idx.size) < acc[s]
        v = np.where(correct, 0,
                     rng.integers(1, spec.n_false + 1, size=idx.size))
        values[s, idx] = v

    copies: set = set()
    copy_edges: list = []
    originals = rng.choice(S, size=spec.n_cliques, replace=False)
    used = set(originals.tolist())
    for o in originals:
        if spec.clique_items is not None:
            k = spec.clique_items
            values[o, :] = -1
            idx = rng.choice(D, size=k, replace=False)
            correct = rng.random(k) < acc[o]
            values[o, idx] = np.where(
                correct, 0, rng.integers(1, spec.n_false + 1, size=k))
        elif (values[o] >= 0).sum() < 20:
            idx = rng.choice(D, size=20, replace=False)
            correct = rng.random(20) < acc[o]
            values[o, idx] = np.where(
                correct, 0, rng.integers(1, spec.n_false + 1, size=20))
        members = []
        for _ in range(spec.clique_size - 1):
            c = int(rng.integers(0, S))
            while c in used:
                c = int(rng.integers(0, S))
            used.add(c)
            members.append(c)
        o_idx = np.nonzero(values[o] >= 0)[0]
        for c in members:
            if spec.clique_items is not None:
                values[c, :] = -1
            take = o_idx[rng.random(o_idx.size) < spec.copy_selectivity]
            values[c, take] = values[o, take]
            copy_edges.append((c, int(o)))
            copies.add((min(c, int(o)), max(c, int(o))))
        for a in members:
            for b in members:
                if a < b:
                    copies.add((a, b))
    return SyntheticClaims(values=values, accuracy=acc, copies=copies,
                           copy_edges=copy_edges)


def book_full_spec(seed: int = 0) -> SyntheticSpec:
    """Book-full: 3,182 sources x 20,000 items, long-tail coverage."""
    return SyntheticSpec(n_sources=3182, n_items=20000, coverage="book",
                         n_cliques=60, clique_size=3, seed=seed)


def stock_2wk_spec(seed: int = 0) -> SyntheticSpec:
    """Stock-2wk: 55 sources x 80,000 items, dense coverage."""
    return SyntheticSpec(n_sources=55, n_items=80000, coverage="stock",
                         n_cliques=6, clique_size=3, seed=seed)


def oracle_claim_probs(values: np.ndarray) -> np.ndarray:
    """Truth prior per claim: value 0 (the truth) .95, any other value .02."""
    return np.where(values == 0, 0.95,
                    np.where(values > 0, 0.02, 0.0)).astype(np.float32)


def synthetic_query_rows(values_corpus: np.ndarray, n_rows: int,
                         copy_fraction: float = 0.7, p_copier: float = 0.6,
                         items_per_row: int = 24, seed: int = 0):
    """Query rows shaped like the corpus.

    A row is a copier with probability ``p_copier`` (it copies
    ``copy_fraction`` of a random corpus source's claims and fills 6 items
    independently) or an independent row, which claims ``items_per_row``
    random items. Returns ``(values, accuracy, p_claim,
    origins)``; ``origins[r]`` is the copied corpus row or -1.
    """
    rng = np.random.default_rng(seed)
    S, D = values_corpus.shape
    n_false = int(max(values_corpus.max(), 1))
    values = -np.ones((n_rows, D), dtype=np.int32)
    accuracy = rng.uniform(0.35, 0.95, n_rows).astype(np.float32)
    origins = np.full(n_rows, -1, dtype=np.int32)
    for r in range(n_rows):
        if rng.random() < p_copier:
            o = int(rng.integers(0, S))
            o_idx = np.nonzero(values_corpus[o] >= 0)[0]
            take = o_idx[rng.random(o_idx.size) < copy_fraction]
            values[r, take] = values_corpus[o, take]
            origins[r] = o
            fill = rng.choice(D, size=min(6, D), replace=False)
        else:
            fill = rng.choice(D, size=min(items_per_row, D), replace=False)
        fill = fill[values[r, fill] < 0]
        correct = rng.random(fill.size) < accuracy[r]
        values[r, fill] = np.where(
            correct, 0, rng.integers(1, n_false + 1, size=fill.size))
    return values, accuracy, oracle_claim_probs(values), origins


def orders(seed: int, n_sources: int, n_items: int):
    """An order of the sources and one of the items, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.permutation(n_sources), rng.permutation(n_items)


SPECS = {"book_full": book_full_spec, "stock_2wk": stock_2wk_spec}


def spec_from_config(cfg: dict, seed: int) -> SyntheticSpec:
    """The ``SyntheticSpec`` a configuration file's ``spec`` block names."""
    s = cfg["spec"]
    return SyntheticSpec(
        n_sources=int(s["n_sources"]), n_items=int(s["n_items"]),
        n_false=int(s.get("n_false", 50)), coverage=s["coverage"],
        n_cliques=int(s["n_cliques"]), clique_size=int(s["clique_size"]),
        copy_selectivity=float(s.get("copy_selectivity", 0.8)),
        seed=seed)
