"""The vote kernel's share of its bf16 roofline, from the trace's kernel
time.

Per vote (a ``truth.vote`` span, with the resident incidence's padded
shape ``s_pad`` x ``e_pad`` and its ``sources`` x ``e_all`` live part):
three bf16 products of the (s_pad, s_pad) weights with the (s_pad, e_pad)
incidence, 3 x 2 s_pad^2 e_pad operations; and the incidence read once,
sources x e_all int8 bytes.
"""


def vote_work(attrs: dict) -> tuple[float, float]:
    """(bf16 operations, bytes) of one vote."""
    s_pad, e_pad = float(attrs["s_pad"]), float(attrs["e_pad"])
    return 3 * 2 * s_pad * s_pad * e_pad, float(attrs["sources"]) * float(
        attrs["e_all"])


def read(run):
    if run.trace is None or run.peak is None:
        return None
    try:
        from repro.utils import trace
    except ImportError:
        return None
    kernel_s = run.trace["kernel_s"].get("vote", 0.0)
    w0, w1 = run.window
    votes = [r for r in trace.records(w0, w1)
             if r.name == "truth.vote" and r.value is None
             and "s_pad" in r.attrs]
    if kernel_s <= 0 or not votes:
        return None
    ops = nbytes = 0.0
    for r in votes:
        o, b = vote_work(r.attrs)
        ops, nbytes = ops + o, nbytes + b
    t_ops = ops / float(run.peak["bf16_flops_per_s"])
    t_mem = nbytes / float(run.peak["hbm_bytes_per_s"])
    bound = "compute" if t_ops >= t_mem else "memory"
    share = 100.0 * max(t_ops, t_mem) / kernel_s
    print(f"[bench] vote: {len(votes)} votes, {ops:.4g} bf16 ops, "
          f"{nbytes:.4g} bytes in {kernel_s:.4f} s of kernel time, "
          f"{bound}-bound, {share:.3f} % of the roofline", flush=True)
    return share
