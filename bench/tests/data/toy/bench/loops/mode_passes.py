"""Whole-corpus detection passes in the traffic's ``mode``, each answer
row compared with the plain reference."""
import time

import drivers
from check import Tally, compare_rows


class ModePasses(drivers.Driver):
    """``DetectionEngine(mode=traffic["mode"]).detect`` back to back."""

    def setup(self, seconds):
        from repro.core.engine import DetectionEngine

        self.engine = DetectionEngine(self.copy_cfg, mode=self.traffic["mode"],
                                      **self.engine_options)
        for _ in range(int(self.traffic["warm_passes"])):
            self.engine.detect(self.ds, self.p)

    def measure(self, seconds):
        self.t0 = time.perf_counter()
        while not self.passes or time.perf_counter() - self.t0 < seconds:
            p0 = time.perf_counter()
            self.result = self.engine.detect(self.ds, self.p)
            self.passes.append({"t0": p0, "t1": time.perf_counter(),
                                "stats": dict(self.engine.last_stats)})
        self.window_end = time.perf_counter()
        self.attempted = len(self.passes)

    def end_to_end(self):
        return {"toy_pass_s": (self.window_end - self.t0) / len(self.passes)}

    def release(self):
        del self.engine

    def check(self, control=None):
        res = self.result
        k = int(self.cfg["guarantees"]["check_rows"])
        rows = self.sample(list(range(self.values.shape[0])), k, 7)
        tally = Tally(self.cell.limits["near_score_rel_gap"])
        compare_rows(tally, self.values[rows], self.p[rows],
                     self.accuracy[rows], self.values, self.p, self.accuracy,
                     res.copying[rows], self.model, self.exact_band,
                     c_fwd=res.c_fwd[rows], pr=res.pr_independent[rows],
                     skip=rows, control=control)
        return {"decision_mismatch": tally.decision_mismatch,
                "near_score_rel_gap": tally.near_score_rel_gap}
