"""chip_smoke.py's phases at smoke size on the CPU.

The chip run itself needs a TPU; these tests rehearse its control flow —
the same phase functions, called directly with ``chip_smoke.SMOKE`` sizes
— so a change that would break the chip run breaks here first.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # its dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["phase_kernel_parity", "phase_serve",
                                   "phase_train"])
def test_chip_smoke_phase_at_smoke_size(chip_smoke, phase, monkeypatch,
                                        tmp_path):
    # keep the entry points from pointing this process's compile cache
    # at the repository
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    getattr(chip_smoke, phase)(chip_smoke.SMOKE)


def _run(code_or_args, devices=1, cache_dir=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run([sys.executable, *code_or_args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def test_chip_smoke_schemes_phase_on_four_host_devices(tmp_path):
    """The --chips 4 phase (ring vs StarTrail at P=4) on 4 CPU devices."""
    p = _run(["-c", "import chip_smoke; "
              "chip_smoke.phase_schemes(chip_smoke.SMOKE)"], devices=4,
             cache_dir=tmp_path)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "ring vs startrail step-1 loss" in p.stdout


def test_chip_smoke_refuses_a_host_without_tpu(tmp_path):
    p = _run(["chip_smoke.py"], cache_dir=tmp_path)
    assert p.returncode != 0
    assert "not a TPU" in p.stdout
    assert '"ok"' not in p.stdout
    assert "phase_" not in p.stdout
