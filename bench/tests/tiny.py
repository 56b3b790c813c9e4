"""Tiny copies of the benchmark's cells for CPU rehearsals.

A cell's configuration and traffic file each carry a ``tiny`` block: the
cut that a rehearsal merges into it, a nested block key by key (``{}``
where the file needs none). A file without one is an error, not a
rehearsal at the deployment's size.
"""
import copy
import time

import harness


def merge(into: dict, cut: dict) -> None:
    """Write ``cut`` over ``into``, descending into blocks both hold."""
    for key, value in cut.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            merge(into[key], value)
        else:
            into[key] = value


def tiny_cell(name: str, root=harness.ROOT) -> harness.Cell:
    """The cell ``name`` cut to a CPU-sized corpus by its files' ``tiny``
    blocks."""
    cell = copy.deepcopy(harness.load_cell(name, root))
    for kind, block in (("configs", cell.config), ("traffic", cell.traffic)):
        if "tiny" not in block:
            key = "config" if kind == "configs" else "traffic"
            raise KeyError(f"bench/{kind}/{cell.workload[key]}.json has no "
                           f"tiny block, so {name} has no CPU cut")
        merge(block, block["tiny"])
    return cell


def run_tiny(name: str, seed: int = 5, seconds: float = 2.0,
             control=None, cell=None, root=harness.ROOT,
             trace: bool = False) -> dict:
    """One rehearsal run of a tiny cell on the CPU."""
    import jax

    return harness.run_cell(cell or tiny_cell(name, root), seed, seconds,
                            trace, jax.devices()[:1], time.perf_counter(),
                            control=control)
