"""The toyscore kernel's share of its bf16 roofline: 2 S^2 D operations
and 2 S D bytes per pass, over the trace's toyscore time."""


def read(run):
    if run.trace is None or run.peak is None:
        return None
    kernel_s = run.trace["kernel_s"]["toyscore"]
    spec = run.cell.config["spec"]
    S, D = float(spec["n_sources"]), float(spec["n_items"])
    n = len(run.passes_in_window())
    if kernel_s <= 0 or n == 0:
        return None
    t_ops = n * 2 * S * S * D / float(run.peak["bf16_flops_per_s"])
    t_mem = n * 2 * S * D / float(run.peak["hbm_bytes_per_s"])
    return 100.0 * max(t_ops, t_mem) / kernel_s
