"""The built-in loop kinds a traffic file can name; a loop of a cell's
own is the ``Driver`` subclass in ``bench/loops/<loop>.py``
(``harness.loop_class``).

* ``closed``: ``clients`` callers, each with one detect request
  outstanding; the window ends when the last request issued before the
  deadline completes. Reports ``detect_req_per_s``.
* ``corpus``: whole-corpus detection passes back to back. Reports
  ``corpus_pass_s``.

Each driver builds the corpus from the seed, sets up the program, warms
up, measures, and afterwards compares a sample of its answers with the
plain reference (``check.py``).
"""
from __future__ import annotations

import functools
import math
import threading
import time

import numpy as np

import gen
from check import Tally, compare_row, compare_rows
from harness import CompileCounter, install_hooks, log, sub_seed


class Driver:
    """Shared set-up: corpus from the seed, model, counters."""

    def __init__(self, cell, seed, spans):
        from repro.core.types import ClaimsDataset, CopyConfig

        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.engine_options = dict(cell.config.get("engine", {}))
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.model = self.cfg["model"]
        self.copy_cfg = CopyConfig(alpha=self.model["alpha"],
                                   s=self.model["s"], n=self.model["n"])
        self.exact_band = float(self.cfg["guarantees"]["exact_band"])
        # one corpus and one request stream for every run, in the order the
        # seed deals: every seed does the same work
        self.data_seed = int(self.cfg["spec"]["data_seed"])
        t0 = time.perf_counter()
        sc = gen.synthetic_claims(gen.spec_from_config(self.cfg,
                                                       self.data_seed))
        self.base_values = sc.values
        self.src_order, self.item_order = gen.orders(sub_seed(seed, 0),
                                                     *sc.values.shape)
        self.values = sc.values[self.src_order][:, self.item_order]
        self.accuracy = sc.accuracy[self.src_order]
        self.p = gen.oracle_claim_probs(self.values)
        self.ds = ClaimsDataset(values=self.values, accuracy=self.accuracy)
        log(f"corpus {self.values.shape[0]} sources x {self.values.shape[1]}"
            f" items, {int((self.values >= 0).sum())} claims, generated in "
            f"{time.perf_counter() - t0:.2f} s")
        self.passes: list = []
        self.counters: dict = {}
        self.attempted = 0
        self.failed = 0
        self.window_end = None
        self._extra_notes: list = []

    # -- query rows ---------------------------------------------------------

    def make_rows(self, n_rows: int, stream: int):
        """``n_rows`` query rows of a stream: the traffic's law over the
        corpus, with the items in the run's order."""
        t = self.traffic
        v, a, _, _ = gen.synthetic_query_rows(
            self.base_values, n_rows, copy_fraction=t["copy_fraction"],
            p_copier=t["p_copier"], items_per_row=t["items_per_row"],
            seed=sub_seed(self.data_seed, stream))
        v = v[:, self.item_order]
        return v, a, gen.oracle_claim_probs(v)

    def make_request(self, rid: int, stream: int):
        from repro.core.serving import DetectRequest

        v, a, p = self.make_rows(self.traffic["rows_per_request"], stream)
        return DetectRequest(rid=rid, values=v, accuracy=a, p_claim=p)

    def service(self, **extra):
        from repro.core.serving import DetectionService

        kw = {**self.cfg["service"], **self.engine_options, **extra}
        return DetectionService(self.ds, self.p, self.copy_cfg, **kw)

    def hooks(self, svc=None) -> None:
        """Spans on the program's calls; each pass records its requests,
        the service's epoch and the engine's counters."""

        def start(args, kwargs):
            reqs = args[3] if len(args) > 3 else kwargs["requests"]
            return {"t0": time.perf_counter(), "engine": args[2],
                    "rids": [r.rid for r in reqs],
                    "epoch": None if svc is None else svc.epoch}

        def end(ctx, result):
            eng = ctx.pop("engine")
            ctx.update(t1=time.perf_counter(), stats=dict(eng.last_stats))
            self.passes.append(ctx)

        install_hooks(self.spans, start, end)

    def notes(self) -> list:
        return self._extra_notes

    def release(self) -> None:
        """Free the program's state after the window (before the check)."""

    def sample(self, items: list, k: int, stream: int) -> list:
        """A sample of ``k`` items drawn from the seed."""
        if len(items) <= k:
            return list(items)
        rng = np.random.default_rng(sub_seed(self.seed, stream))
        idx = rng.choice(len(items), size=k, replace=False)
        return [items[i] for i in sorted(idx)]


# ---------------------------------------------------------------------------
# closed loop
# ---------------------------------------------------------------------------

class ClosedLoop(Driver):
    """``clients`` callers, each waiting for its answer before the next."""

    def setup(self, seconds: float) -> None:
        t = self.traffic
        self.clients = int(t["clients"])
        t0 = time.perf_counter()
        self.svc = self.service()
        log(f"service built in {time.perf_counter() - t0:.2f} s")
        self.hooks(self.svc)
        batch = int(self.cfg["service"]["max_batch_requests"])
        with CompileCounter() as warm_compiles:
            t0 = time.perf_counter()
            futs = [self.svc.submit(self.make_request(-1, 10_000 + i))
                    for i in range(batch)]
            self.svc.flush()
            for f in futs:
                f.result()
            warm_s = time.perf_counter() - t0
        compile_s = sum(e[1] for e in warm_compiles.events)
        log(f"warm batch: {warm_s:.2f} s, {compile_s:.2f} s of it compiling")
        # the window's requests are made here, not in the window: client c
        # sends rids c, c + clients, ..., and each pass answers ``batch``
        pass_s = max(warm_s - compile_s, 0.05)
        n_plan = min(int(t["max_planned_batches"]),
                     math.ceil(1.5 * seconds / pass_s) + 4)
        self.planned = {rid: self.make_request(rid, 1_000_000 + rid)
                        for rid in range(n_plan * batch)}
        self.passes.clear()
        self.svc.stats.queue_wait_samples.clear()
        self.done: dict = {}      # rid -> (request, response or exception, t)
        self._first = [self.request(c) for c in range(self.clients)]

    def request(self, rid: int):
        """The request ``rid``: planned in the set-up, or made now."""
        req = self.planned.get(rid)
        return req if req is not None else self.make_request(
            rid, 1_000_000 + rid)

    def measure(self, seconds: float) -> None:
        svc = self.svc
        lock = threading.Lock()
        t_close = time.perf_counter() + seconds

        def record(req, fut):
            try:
                out = fut.result()
            except Exception as e:              # noqa: BLE001
                out = e
            with lock:
                self.done[req.rid] = (req, out, time.perf_counter())

        def client(c: int, first) -> None:
            req, k = first, 0
            while True:
                fut = self.futs[c] if k == 0 else svc.submit(req)
                fut.add_done_callback(functools.partial(record, req))
                try:
                    fut.result()
                except Exception:               # noqa: BLE001
                    pass
                if time.perf_counter() >= t_close:
                    return
                k += 1
                req = self.request(k * self.clients + c)

        # every client's first request is queued before the worker starts,
        # so the first passes are full batches too
        self.futs = [svc.submit(r) for r in self._first]
        self.t0 = time.perf_counter()
        svc.start()
        threads = [threading.Thread(target=client, args=(c, self._first[c]),
                                    daemon=True)
                   for c in range(self.clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(seconds + 600)
        svc.stop()
        self.window_end = max((d[2] for d in self.done.values()),
                              default=time.perf_counter())
        self.attempted = len(self.done)
        self.failed = sum(isinstance(d[1], Exception)
                          for d in self.done.values())
        self.counters["queue_wait_s"] = list(svc.stats.queue_wait_samples)

    def end_to_end(self) -> dict:
        ok = self.attempted - self.failed
        return {"detect_req_per_s": ok / (self.window_end - self.t0)}

    def notes(self) -> list:
        sizes = [len(p["rids"]) for p in self.passes]
        return [f"{self.attempted} requests, {self.failed} failed, "
                f"{len(sizes)} passes of {sizes} requests"]

    def release(self) -> None:
        del self.svc

    def check(self, control=None) -> dict:
        """The numbers compared for a sample of the window's answers; with
        ``control`` (a dtype) for the reference in that precision put in
        the program's place, on the same sample."""
        answered = [d for d in self.done.values()
                    if not isinstance(d[1], Exception)]
        answered.sort(key=lambda d: d[0].rid)
        k = int(self.cfg["guarantees"]["check_requests"])
        picked = self.sample(answered, k - 1, 7)
        longest = max(answered, key=lambda d: int((d[0].values >= 0).sum()),
                      default=None)
        if longest is not None and all(longest is not d for d in picked):
            picked.append(longest)
        tally = Tally(self.cell.limits["near_score_rel_gap"])
        for req, resp, _ in picked:
            self._compare(tally, req, resp, self.values, self.p,
                          self.accuracy, control)
        log(f"compared {len(picked)} requests: {tally.pairs} pairs, "
            f"{tally.near_pairs} near the threshold (largest gap "
            f"{tally.near_score_gap:.6g}), {tally.rounding_band} inside "
            f"float32 rounding of it")
        return {"decision_mismatch": tally.decision_mismatch,
                "near_score_rel_gap": tally.near_score_rel_gap,
                "unanswered": self.failed}

    def _compare(self, tally, req, resp, c_values, c_p, c_acc,
                 control=None) -> None:
        compare_rows(tally, req.values, req.p_claim, req.accuracy, c_values,
                     c_p, c_acc, resp.copying, self.model, self.exact_band,
                     c_fwd=resp.c_fwd, pr=resp.pr_independent,
                     control=control)
        compare_rows(tally, req.values, req.p_claim, req.accuracy,
                     req.values, req.p_claim, req.accuracy,
                     resp.intra_copying, self.model, self.exact_band,
                     skip=list(range(req.n_rows)), control=control)


# ---------------------------------------------------------------------------
# whole-corpus passes
# ---------------------------------------------------------------------------

#: Pairs whose served decision log-odds lie within this of the threshold
#: are the corpus check's second sample.
NEAR_SELECT = 2.0


class CorpusPasses(Driver):
    """``DetectionEngine.detect`` over the whole corpus, back to back."""

    def setup(self, seconds: float) -> None:
        from repro.core.engine import DetectionEngine

        self.engine = DetectionEngine(self.copy_cfg,
                                      mode=self.cfg["service"]["mode"],
                                      **self.engine_options)
        self.hooks()
        for w in range(int(self.traffic.get("warm_passes", 1))):
            t0 = time.perf_counter()
            self.engine.detect(self.ds, self.p)
            log(f"warm pass {w}: {time.perf_counter() - t0:.2f} s")

    def measure(self, seconds: float) -> None:
        t0 = self.t0 = time.perf_counter()
        self.n = 0
        while True:
            p0 = time.perf_counter()
            self.result = self.engine.detect(self.ds, self.p)
            self.passes.append({"t0": p0, "t1": time.perf_counter(),
                                "rids": [], "epoch": None,
                                "stats": dict(self.engine.last_stats)})
            self.n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_end = time.perf_counter()
        self.attempted = self.n

    def end_to_end(self) -> dict:
        return {"corpus_pass_s": (self.window_end - self.t0) / self.n}

    def notes(self) -> list:
        st = self.passes[-1]["stats"]
        return [f"{self.n} passes; last: {st.get('chunks')} chunks of "
                f"{st.get('chunk_width')}, {st.get('tiles_kept')}/"
                f"{st.get('tiles_total')} tiles, {st.get('rescored_pairs')} "
                f"pairs rescored"]

    def release(self) -> None:
        del self.engine

    def check(self, control=None) -> dict:
        res = self.result
        S = self.values.shape[0]
        k = int(self.cfg["guarantees"]["check_rows"])
        rows = self.sample(list(range(S)), k - 1, 7)
        longest = int(np.argmax((self.values >= 0).sum(axis=1)))
        if longest not in rows:
            rows.append(longest)
        tally = Tally(self.cell.limits["near_score_rel_gap"])
        for i in rows:
            compare_rows(tally, self.values[i:i + 1], self.p[i:i + 1],
                         self.accuracy[i:i + 1], self.values, self.p,
                         self.accuracy, res.copying[i:i + 1], self.model,
                         self.exact_band, c_fwd=res.c_fwd[i:i + 1],
                         pr=res.pr_independent[i:i + 1], skip=[i],
                         control=control)
        # and the pairs the pass itself placed near the threshold, where
        # an approximate score would flip a decision: up to
        # ``check_near_pairs`` of them, drawn from the seed
        pr = res.pr_independent
        with np.errstate(divide="ignore"):
            z = np.log((1.0 - pr) / pr)
        pi, pj = np.nonzero(np.triu((pr < 1.0) & (np.abs(z) < NEAR_SELECT),
                                    1))
        n_sel = len(pi)
        cap = int(self.cfg["guarantees"]["check_near_pairs"])
        if n_sel > cap:
            keep = np.sort(np.random.default_rng(
                sub_seed(self.seed, 8)).choice(n_sel, cap, replace=False))
            pi, pj = pi[keep], pj[keep]
        for i in np.unique(pi):
            cols = pj[pi == i]
            compare_row(tally, self.values[i], self.p[i], self.accuracy[i],
                        self.values[cols], self.accuracy[cols],
                        res.copying[i, cols], self.model, self.exact_band,
                        c_fwd=res.c_fwd[i, cols], pr=pr[i, cols],
                        control=control)
        log(f"compared {len(rows)} rows and {len(pi)} of {n_sel} pairs the "
            f"pass put near the threshold: {tally.pairs} pairs, "
            f"{tally.near_pairs} near it by the reference (largest gap "
            f"{tally.near_score_gap:.6g}), {tally.rounding_band} inside "
            f"float32 rounding of it")
        return {"decision_mismatch": tally.decision_mismatch,
                "near_score_rel_gap": tally.near_score_rel_gap}


DRIVERS = {"closed": ClosedLoop, "corpus": CorpusPasses}
