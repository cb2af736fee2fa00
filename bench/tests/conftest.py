"""The benchmark's tests: the repo root and ``src/`` on the path, and a
tiny copy of the benchmark to drive on the CPU (``tiny.py``)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny_root(tmp_path):
    from bench.tests.tiny import make_tiny_root

    return make_tiny_root(tmp_path)
