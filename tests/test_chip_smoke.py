"""chip_smoke.py: its phases at a tiny shape on the CPU (Pallas in interpret
mode), and its refusal to report a result without a TPU or without the repo.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.data.claims import SyntheticSpec

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(n_sources=150, n_items=600, coverage="book", n_cliques=5,
            clique_size=3, seed=0)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, extra_env=None, timeout=600):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_one_chip_phases_tiny_interpret():
    smoke = _load_smoke()
    smoke.one_chip(SyntheticSpec(**TINY), impl="interpret", slice_sources=96)


def test_four_chip_phases_tiny_on_virtual_devices():
    script = textwrap.dedent(f"""
        import importlib.util
        from repro.data.claims import SyntheticSpec
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.four_chips(SyntheticSpec(**{TINY!r}), impl="ref")
    """)
    proc = _run(["-c", script], ROOT, {
        "PYTHONPATH": str(ROOT / "src"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "2x2 data x pod mesh == 1 device" in proc.stdout, proc.stdout


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "script_alone"])
def test_script_fails_without_tpu_or_repo(alone, tmp_path):
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        proc = _run(["chip_smoke.py"], tmp_path)
    else:
        proc = _run([str(ROOT / "chip_smoke.py")], tmp_path)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
