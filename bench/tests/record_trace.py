"""Record the small TPU trace that ``test_trace_reduce.py`` reduces.

    python3 bench/tests/record_trace.py > bench/tests/data/tpu_trace_small.json

Run on a machine with one TPU. Inside a ``bench.window`` annotation it runs
a jitted matmul twice with a sleep between, and writes the host and device
planes the reduction reads, with the expected numbers counted here in a
different way: the window from the annotation, busy time as the summed
length of the merged op intervals found by a scan over a 1 us grid.
"""
import glob
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    mm(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        mm(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.01)
        mm(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    planes = []
    for name, lines in trace_reduce.planes_from_file(path):
        if name.startswith("/host"):
            keep = {k: [e for e in v if e[0].startswith("bench.")]
                    for k, v in lines.items()}
            keep = {k: v for k, v in keep.items() if v}
        elif name.startswith("/device:") and trace_reduce.OPS_LINE in lines:
            keep = {trace_reduce.OPS_LINE: lines[trace_reduce.OPS_LINE]}
        else:
            continue
        if keep:
            planes.append([name, keep])
    host = [e for n, l in planes if n.startswith("/host")
            for v in l.values() for e in v]
    w0, w1 = next((e[1], e[2]) for e in host if e[0] == "bench.window")
    dev0 = next(n for n, _ in planes if n.startswith("/device:"))
    ops = dict(planes)[dev0][trace_reduce.OPS_LINE]
    grid = np.zeros(int((w1 - w0) // 1000) + 1, bool)
    for _, s, e in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) // 1000):int((e - w0) // 1000)] = True
    kernel = sum(max(0.0, min(e, w1) - max(s, w0)) for n, s, e in ops
                 if "dot" in n or "convolution" in n or "fusion" in n)
    planes = [[n, l] for n, l in planes
              if n.startswith("/host") or n == dev0]
    json.dump({"planes": planes, "kernel_pattern": "dot|convolution|fusion",
               "expected": {"window_s": (w1 - w0) * 1e-9,
                            "busy_s": float(grid.sum()) * 1e-6,
                            "n_ops": len(ops),
                            "kernel_s": kernel * 1e-9}},
              sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
