"""Whole copy-aware truth-finding runs back to back, each round checked
against the plain references.

A run is ``truth_finding(ds, cfg, detector=..., tol=..., max_rounds=...)``
from ``init_accuracy``: a round-0 vote, then ``max_rounds`` rounds of
detection and vote (``tol`` 0 runs them all). It reports
``corpus_pass_s``, the window's time over its whole runs.

The check takes the last run's rounds one by one, each from its own
inputs, since each round makes its own probabilities and accuracies:

* ``vote_p_gap``, ``acc_gap``: the largest gap, over rounds, between the
  program's P of every claim and its accuracies and the float64 vote
  (``truth_reference.vote``) given that round's accuracies and copy
  probabilities;
* ``decision_mismatch``: per detection round, ``check_rows`` rows (the
  longest among them) against the float64 copy scores
  (``reference.row_scores``) under that round's accuracies and claim
  probabilities;
* ``rounds_short``: rounds the run left out of ``max_rounds``.

The control puts both references, computed in bfloat16, in the program's
place.
"""
import time

import numpy as np

import drivers
import truth_reference
from check import Tally, compare_rows
from harness import install_hooks, log

#: Program calls timed as bench spans (for the trace's idle gaps):
#: (module, attribute, span).
TRUTH_HOOKS = [
    ("repro.core.truthfind", "build_value_groups", "value_groups"),
    ("repro.core.truthfind", "vote_round", "vote"),
    ("repro.core.engine", "bound_detect", "bound"),
    ("repro.core.incremental", "bound_detect", "bound"),
    ("repro.core.engine", "incremental_detect", "incremental"),
]


class TruthRounds(drivers.Driver):
    """``truth_finding`` over the whole corpus, run after run."""

    def __init__(self, cell, seed, spans):
        super().__init__(cell, seed, spans)
        from repro.core.types import CopyConfig

        m = self.model
        self.copy_cfg = CopyConfig(alpha=m["alpha"], s=m["s"], n=m["n"],
                                   c=m["c"])

    def setup(self, seconds):
        import dataclasses
        import importlib

        from repro.core.truthfind import FusionResult

        if "detections" not in {f.name for f in
                                dataclasses.fields(FusionResult)}:
            raise RuntimeError("truth_finding keeps no per-round detections "
                               "(FusionResult.detections): the check of "
                               "each round cannot run")
        install_hooks(self.spans)
        for mod, attr, name in TRUTH_HOOKS:
            self.spans.wrap(importlib.import_module(mod), attr, name)
        for w in range(int(self.traffic["warm_runs"])):
            t0 = time.perf_counter()
            self.run_once()
            log(f"warm run {w}: {time.perf_counter() - t0:.2f} s")

    def run_once(self):
        from repro.core.truthfind import truth_finding

        t = self.traffic
        self.result = None
        self.result = truth_finding(
            self.ds, self.copy_cfg, detector=t["detector"],
            max_rounds=int(t["max_rounds"]), tol=float(t["tol"]),
            init_accuracy=float(t["init_accuracy"]),
            detector_kwargs=self.engine_options, track_history=True)
        return self.result

    def measure(self, seconds):
        self.t0 = time.perf_counter()
        while not self.passes or time.perf_counter() - self.t0 < seconds:
            p0 = time.perf_counter()
            self.run_once()
            self.passes.append({"t0": p0, "t1": time.perf_counter(),
                                "rids": [], "epoch": None, "stats": {}})
        self.window_end = time.perf_counter()
        self.attempted = len(self.passes)

    def end_to_end(self):
        return {"corpus_pass_s": (self.window_end - self.t0)
                / len(self.passes)}

    def notes(self):
        res = self.result
        per_round = [c.pairs_considered for c in res.counters]
        return [f"{len(self.passes)} runs of "
                f"{[round(p['t1'] - p['t0'], 3) for p in self.passes]} s; "
                f"last: {res.rounds} rounds, pairs considered per round "
                f"{per_round}, fusion accuracy against the generator's "
                f"truth {self.fusion_accuracy(res):.4f}"]

    def fusion_accuracy(self, res) -> float:
        """Share of claimed items whose most probable claimed value is the
        generator's true value (value 0)."""
        v = self.values
        p = np.where(v >= 0, res.p_claim, -1.0)
        best = v[np.argmax(p, axis=0), np.arange(v.shape[1])]
        has = (v >= 0).any(axis=0)
        return float((best[has] == 0).mean())

    def release(self):
        pass

    def check(self, control=None):
        res = self.result
        v = self.values
        S = v.shape[0]
        t = self.traffic
        rows, cols = np.nonzero(v >= 0)
        entry = res.groups.claim_entry[rows, cols]
        acc_in = [np.full(S, float(t["init_accuracy"]), np.float32)]
        acc_in += res.accuracy_history[:-1]
        k = int(self.cfg["guarantees"]["check_rows"])
        longest = int(np.argmax((v >= 0).sum(axis=1)))
        tally = Tally(float(self.cfg["guarantees"]["rounding_band"]))
        p_gap = acc_gap = 0.0
        for r in range(res.rounds + 1):
            acc = acc_in[r]
            pr_copy = (np.zeros((S, S), np.float32) if r == 0 else
                       1.0 - res.detections[r - 1].pr_independent)
            ref_p, ref_acc = truth_reference.vote(v, acc, pr_copy,
                                                  self.model)
            if control is None:
                got_p = res.p_history[r][entry]
                got_acc = res.accuracy_history[r]
            else:
                got_p, got_acc = truth_reference.vote(v, acc, pr_copy,
                                                      self.model, control)
            p_gap = max(p_gap, float(np.abs(got_p - ref_p).max(initial=0)))
            acc_gap = max(acc_gap, float(np.abs(got_acc - ref_acc).max()))
            if r == 0:
                continue
            # the round's detection ran on the last round's outputs
            p_claim = np.zeros(v.shape, np.float32)
            p_claim[rows, cols] = res.p_history[r - 1][entry]
            det = res.detections[r - 1]
            picked = self.sample(list(range(S)), k - 1, 7 + r)
            if longest not in picked:
                picked.append(longest)
            for i in picked:
                compare_rows(tally, v[i:i + 1], p_claim[i:i + 1],
                             acc[i:i + 1], v, p_claim, acc,
                             det.copying[i:i + 1], self.model,
                             self.exact_band, skip=[i], control=control)
        log(f"compared {res.rounds + 1} votes and {tally.pairs} pairs over "
            f"{res.rounds} detection rounds ({tally.rounding_band} inside "
            f"float32 rounding of the threshold): P gap {p_gap:.3g}, "
            f"accuracy gap {acc_gap:.3g}")
        return {"decision_mismatch": tally.decision_mismatch,
                "vote_p_gap": p_gap, "acc_gap": acc_gap,
                "rounds_short": int(t["max_rounds"]) - res.rounds}
