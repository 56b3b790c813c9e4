"""Tiny copies of the benchmark's cells for CPU rehearsals."""
import copy
import time

import harness

TINY_SPEC = {"book_full": {"n_sources": 60, "n_items": 400, "n_cliques": 4}}
TINY_TRAFFIC = {"serve": {"clients": 4}, "corpus": {}}


def tiny_cell(name: str) -> harness.Cell:
    """The cell ``name`` cut to a CPU-sized corpus."""
    cell = copy.deepcopy(harness.load_cell(name))
    cell.traffic.update(TINY_TRAFFIC[cell.workload["traffic"]])
    cell.config["spec"].update(TINY_SPEC[cell.workload["config"]])
    cell.config["service"]["max_pending_rows"] = 64
    return cell


def run_tiny(name: str, seed: int = 5, seconds: float = 2.0,
             control=None, cell=None) -> dict:
    """One rehearsal run of a tiny cell on the CPU."""
    import jax

    return harness.run_cell(cell or tiny_cell(name), seed, seconds, False,
                            jax.devices()[:1], time.perf_counter(),
                            control=control)
