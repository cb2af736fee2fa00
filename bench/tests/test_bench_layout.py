"""BENCHMARK.json against the benchmark's rules, and every piece found by
name."""

import json
import re
import subprocess
import sys

import pytest

from bench import harness

from bench.tests.tiny import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.load_cell(ROOT, cell)
    assert c.driver_path().is_file()
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert c.metric_path(m["name"]).is_file()
        assert m["moves"] in e2e


def test_names_units_and_bounds():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += CELLS + [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        for w in m.get("workloads", CELLS):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", CELLS), (m["name"], w)


def test_configs_and_chips():
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size")), key


def test_a_new_cell_is_new_files_only(tiny_root):
    """A cell, traffic mix and per-layer metric added as new files and
    entries are found; no existing file changes."""
    bench = tiny_root / "bench"
    (bench / "traffic" / "tiny_serve_more.json").write_text(
        (bench / "traffic" / "tiny_serve.json").read_text())
    (bench / "metrics" / "tokens_seen.extra.py").write_text(
        "def read(trace, facts):\n    return float(facts['tokens'])\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.serve_more", "config": "tiny",
                              "traffic": "tiny_serve_more", "chips": 1,
                              "why": "a later cell"})
    spec["per_layer"].append({
        "name": "tokens_seen.extra", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "serve_tokens_per_s", "workloads": ["tiny.serve_more"]})
    for m in spec["end_to_end"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append("tiny.serve_more")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(tiny_root, "tiny.serve_more")
    assert cell.traffic["kind"] == "serve"
    assert [m["name"] for m in cell.per_layer] == ["tokens_seen.extra"]
    reader = harness.load_module(cell.metric_path("tokens_seen.extra"), "t_")
    assert reader.read(None, {"tokens": 7}) == 7.0


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


def test_importing_the_benchmark_touches_no_accelerator():
    """No benchmark module describes a topology or starts JAX when it is
    imported."""
    code = ("import sys; sys.path.insert(0, %r); "
            "import bench.harness, bench.counts, bench.trace, bench.kernels, "
            "bench.workload, bench.reference; "
            "print('jax' in sys.modules)" % str(ROOT))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
