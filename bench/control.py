"""Read the numbers the output check compares, for the program and for
its control, over several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 15

Each seed is one run of the cell as ``bench/run.py`` makes it. After the
window the same sample of answers is compared twice: the program's, and
the control's, which is the plain reference computed in bfloat16 (the
precision below the float32 the configuration states for scores) in the
program's place. The limits in ``bench/limits/<cell>.json`` lie between
the largest reading of the program and the smallest of the control. One
JSON line per seed; this script is not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax

    import harness
    from check import CONTROL_DTYPE
    from repro.runtime.platform import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    devices = devices[:int(cell.workload["chips"])]
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run_cell(cell, seed, args.seconds, False, devices,
                               time.perf_counter(), control=CONTROL_DTYPE)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "metrics": res["metrics"], "checks": res["checks"],
                          "control": res["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
