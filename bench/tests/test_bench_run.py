"""The command refuses to run anywhere but on a TPU, and without the
program."""

import json
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve.danube.decode",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            continue
    return False


def test_refuses_a_machine_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
    assert "not a TPU" in out.stderr


def test_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert not _printed_result(out.stdout)
