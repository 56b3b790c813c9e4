"""Pipeline parallelism + gradient compression + elastic restore — run on
8 virtual host devices in a subprocess (main process stays single-device)."""
import json
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    results = {}

    # ---------------- pipeline parallelism -------------------------------
    from repro.runtime.pipeline_parallel import pipeline_apply
    mesh = jax.make_mesh((4,), ("stage",))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.5, (n_stages, d, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (n_micro, mb, d)), jnp.float32)

    def stage_fn(w_s, h):
        return jnp.tanh(h @ w_s)

    out = pipeline_apply(stage_fn, w, x, mesh, "stage")

    ref = x
    for s in range(n_stages):
        ref = jnp.tanh(ref @ w[s])
    results["pipeline_err"] = float(jnp.abs(out - ref).max())

    # ---------------- int8 error-feedback compression --------------------
    from repro.optim.compression import compress_allreduce, init_error_state
    mesh2 = jax.make_mesh((8,), ("data",))
    g = jnp.asarray(rng.normal(0, 1, (8, 64)), jnp.float32)

    def local(gs, err):
        s, e = compress_allreduce(gs, err, "data")
        return s, e
    fn = jax.jit(shard_map(local, mesh=mesh2,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P(None), P("data")),
                           check_vma=False))
    summed, err = fn(g, jnp.zeros_like(g))
    exact = g.sum(axis=0)
    rel = float(jnp.abs(summed[0] - exact).max() / jnp.abs(exact).max())
    results["compress_rel_err"] = rel
    # error feedback: the quantization residual is retained per shard
    results["err_nonzero"] = bool(jnp.abs(err).max() > 0)

    # compressed sum + error feedback converges over repeated steps
    acc_err = jnp.zeros_like(g)
    tot_c = jnp.zeros_like(exact)
    tot_x = jnp.zeros_like(exact)
    for i in range(20):
        gi = jnp.asarray(rng.normal(0, 1, (8, 64)), jnp.float32)
        s_i, acc_err = fn(gi, acc_err)
        tot_c = tot_c + s_i[0]
        tot_x = tot_x + gi.sum(axis=0)
    results["compress_drift"] = float(jnp.abs(tot_c - tot_x).max())

    # ---------------- elastic restore (4 → 8 way) ------------------------
    import tempfile
    from repro.checkpoint import save_checkpoint, load_checkpoint
    tmp = tempfile.mkdtemp()
    mesh4 = jax.make_mesh((4,), ("data",))
    arr = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
    sharded4 = jax.device_put(arr, NamedSharding(mesh4, P("data")))
    save_checkpoint(tmp, 1, {"w": sharded4})
    mesh8 = jax.make_mesh((8,), ("data",))
    restored, _ = load_checkpoint(
        tmp, {"w": arr}, shardings={"w": NamedSharding(mesh8, P("data"))})
    results["elastic_err"] = float(jnp.abs(restored["w"] - arr).max())
    results["elastic_nshards"] = len(restored["w"].sharding.device_set)

    print("RESULT" + json.dumps(results))
""")


def test_parallel_features():
    proc = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                          text=True, timeout=600,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    r = json.loads(line[len("RESULT"):])
    assert r["pipeline_err"] < 1e-5, r
    assert r["compress_rel_err"] < 0.05, r
    assert r["err_nonzero"], r
    # error feedback keeps long-run drift far below naive per-step error
    assert r["compress_drift"] < 0.5, r
    assert r["elastic_err"] == 0.0, r
    assert r["elastic_nshards"] == 8, r
