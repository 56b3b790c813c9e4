"""runtime.platform.enable_compile_cache: the persistent compilation cache
goes to ``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise to one
fixed, git-ignored directory of the checkout, whatever the working directory.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import json
    import sys
    import jax
    import jax.numpy as jnp
    from repro.runtime.platform import enable_compile_cache
    where = enable_compile_cache()
    if sys.argv[1] == "compile":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    print(json.dumps({"where": where,
                      "config": jax.config.jax_compilation_cache_dir}))
""")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "unset"])
def test_compile_cache_location(from_env, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    cache = tmp_path / "cache"
    work = tmp_path / "work"
    work.mkdir()
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # compile only where the cache is the test's own directory
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, "compile" if from_env else "locate"],
        cwd=work, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.splitlines()[-1])
    want = str(cache) if from_env else str(ROOT / ".jax_cache")
    assert got == {"where": want, "config": want}
    assert not any(work.iterdir())
    if from_env:
        assert any(cache.iterdir())
    else:
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_engine_keeps_host_arrays_on_heap(monkeypatch):
    """glibc serves chunk-sized arrays from its heap once an engine exists;
    setting it twice is a no-op."""
    import repro.core.engine as engine_mod
    from repro.core import CopyConfig, DetectionEngine
    from repro.runtime import platform

    assert platform.keep_host_arrays_on_heap() is True
    assert platform.keep_host_arrays_on_heap() is True
    calls = []
    monkeypatch.setattr(engine_mod, "keep_host_arrays_on_heap",
                        lambda: calls.append(1))
    DetectionEngine(CopyConfig(alpha=0.1, s=0.8, n=50.0))
    assert calls == [1]
