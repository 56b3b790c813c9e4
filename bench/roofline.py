"""Operations and bytes of the copyscore kernel, and its roofline share.

Per surviving (tile, entry chunk) pair of a tiled pass, with tile edge T
and chunk width b (``DetectionEngine.last_stats``: ``tile``,
``chunk_width``, ``chunk_tiles_run``, ``tiles_kept``):

* the count matmul: 2 T^2 b int8 operations (a multiply and an add per
  term of a T x b by b x T product);
* the incidence read: 2 T b bytes (the row and the column tile, int8);

plus, once per surviving tile and pass, the five T x T float32
accumulators written back (20 T^2 bytes). The count is of the work, not
of the calls, so a change that fuses the chunk loop reads the same work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; a kind not in the table
    is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def copyscore_work(stats: dict) -> tuple[float, float]:
    """(int8 operations, bytes) of one tiled pass from its ``last_stats``."""
    T = float(stats["tile"])
    b = float(stats["chunk_width"])
    run = float(stats["chunk_tiles_run"])
    ops = 2.0 * T * T * b * run
    nbytes = 2.0 * T * b * run + 20.0 * T * T * float(stats["tiles_kept"])
    return ops, nbytes


def roofline_share(ops: float, nbytes: float, kernel_s: float,
                   peak: dict) -> tuple[float, str] | None:
    """Percent of the roofline: the least time the chip could take for the
    work, max(ops / peak ops, bytes / peak bandwidth), over the measured
    kernel time; and which of the two bounds it. None without kernel time
    or work."""
    if kernel_s <= 0 or ops <= 0:
        return None
    t_ops = ops / float(peak["int8_ops_per_s"])
    t_mem = nbytes / float(peak["hbm_bytes_per_s"])
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / kernel_s, bound
