"""``bench/scopes.py`` on the small chip-recorded trace, and the two
readers built on it (``unscoped_share.decode``, ``host_share.decode``) on
timelines made by hand; the readers that came before read the recorded
trace as ``data/expected.json`` says."""

import json
import pathlib
import shutil

import pytest

from bench import harness, scopes, trace
from bench.tests.tiny import ROOT

FIXTURE = pathlib.Path(__file__).parent / "data"
TRACE = FIXTURE / "chip_mini.xplane.pb"
WANT = json.loads((FIXTURE / "expected.json").read_text())


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                               "t_")


def test_fixture_ops_resolve_to_their_scopes():
    tl = scopes.read(TRACE, WANT["devices"])
    by_name = {}
    for _, _, name, scope in tl.devices[0]:
        by_name.setdefault(name, set()).add(scope)
    assert by_name["fusion"] == {"jit(<lambda>)/dot_general"}
    assert by_name["copy-start"] == by_name["copy-done"] == {""}
    # the same operations and times as trace.py's reduction
    red = trace.reduce_file(TRACE, WANT["devices"])
    assert [op[:3] for op in tl.devices[0]] == red.devices[0].ops
    assert tl.window == red.devices[0].window
    assert tl.busy_s() == pytest.approx(WANT["busy_s"], rel=1e-9)
    assert {n for _, _, n in tl.host} == {"engine/step", "engine/decode"}
    # no compute scope in a program without them: everything unscoped
    assert not tl.has_scopes()
    assert tl.scope_seconds() == {"unscoped": pytest.approx(WANT["busy_s"])}
    assert max(tl.idle_gaps(), key=tl.idle_gaps().get) == \
        WANT["top_gap_label"]


@pytest.mark.parametrize("scope,want", [
    ("jit(island)/decode/embed/gather", "decode/embed"),
    ("jit(island)/decode/layers/while/body/closed_call/attn/qkv/dot_general",
     "attn/qkv"),
    ("jit(island)/decode/layers/while/body/closed_call/attn/paged/"
     "jit(paged_decode_attention)/paged_decode_attention/pallas_call",
     "attn/paged"),
    ("jit(island)/prefill/layers/while/body/attn/dot_general", "attn"),
    ("jit(island)/decode/layers/while/body/closed_call/mlp/dot_general",
     "mlp"),
    ("jit(island)/prefill/head/reduce_max", "prefill/head"),
    ("jit(island)/decode/layers/while/body/dynamic_slice", None),
    ("jit(island)/decode/layers/while", None),
    ("a[2]", None),
    ("", None),
])
def test_compute_scope(scope, want):
    assert scopes.compute_scope(scope) == want


def test_trace_found_by_its_window(tmp_path):
    red = trace.reduce_file(TRACE, 1)
    assert scopes.for_reduced(red, tmp_path) is None
    run = tmp_path / ".runs" / "cell" / "trace-1" / "plugins" / "x"
    run.mkdir(parents=True)
    shutil.copy(TRACE, run / "h.xplane.pb")
    tl = scopes.for_reduced(red, tmp_path)
    assert tl is not None and tl.window == red.devices[0].window
    # a reduction of another window finds none
    red.devices[0].window = (0, 1)
    assert scopes.for_reduced(red, tmp_path) is None


def _timeline():
    """Two decode steps of 40 ns each in a 100 ns window: per step an
    unscoped slice copy of 10 ns, a kernel of 20 ns under attn/paged and
    an mlp of 10 ns; the host waits 30 ns of each step, and admits, then
    collects garbage, while the device is idle."""
    ops = []
    for t0 in (10, 60):
        ops += [(t0, t0 + 40, "while.1", "jit(i)/decode/layers/while"),
                (t0, t0 + 10, "copy.2",
                 "jit(i)/decode/layers/while/body/dynamic_slice"),
                (t0 + 10, t0 + 30, "paged_decode_attention.3",
                 "jit(i)/decode/layers/while/body/attn/paged/pallas_call"),
                (t0 + 30, t0 + 40, "fusion.4",
                 "jit(i)/decode/layers/while/body/mlp/dot_general")]
    host = [(0, 52, "engine/step"), (1, 9, "engine/admit"),
            (9, 50, "engine/decode"), (20, 50, "engine/decode_wait"),
            (50, 52, "engine/emit"), (52, 102, "engine/step"),
            (53, 58, "host/gc"), (58, 100, "engine/decode"),
            (70, 100, "engine/decode_wait")]
    return scopes.Timeline((0, 100), [ops], host)


def test_unscoped_share_reader(monkeypatch):
    read = _reader("unscoped_share.decode").read
    monkeypatch.setattr(scopes, "for_reduced", lambda red: _timeline())
    # 2 x 10 ns of 80 ns busy; the loop's own time is nil here
    assert read(None, {}) == pytest.approx(25.0)
    tl = _timeline()
    assert tl.scope_seconds() == pytest.approx(
        {"unscoped": 20e-9, "attn/paged": 40e-9, "mlp": 20e-9})
    from bench import probe

    assert probe.unscoped_ops(tl) == [
        ["copy.2", "jit(i)/decode/layers/while/body/dynamic_slice",
         pytest.approx(20e-9)],
        ["while.1", "jit(i)/decode/layers/while", 0.0]]
    # a program without scopes, and no trace: nothing to read
    bare = scopes.Timeline(tl.window, [[op[:3] + ("",) for op in
                                        tl.devices[0]]], tl.host)
    monkeypatch.setattr(scopes, "for_reduced", lambda red: bare)
    assert read(None, {}) is None
    monkeypatch.setattr(scopes, "for_reduced", lambda red: None)
    assert read(None, {}) is None


def test_host_share_reader(monkeypatch):
    read = _reader("host_share.decode").read
    monkeypatch.setattr(scopes, "for_reduced", lambda red: _timeline())
    # waits cover 30 + 30 ns of the 100 ns window
    assert read(None, {}) == pytest.approx(40.0)
    # the gaps [0, 10) and [50, 60) take the innermost span at their middle
    tl = _timeline()
    assert tl.idle_gaps() == pytest.approx({"engine/admit": 10e-9,
                                            "host/gc": 10e-9})
    bare = scopes.Timeline(tl.window, tl.devices,
                           [h for h in tl.host if "wait" not in h[2]])
    monkeypatch.setattr(scopes, "for_reduced", lambda red: bare)
    assert read(None, {}) is None


def test_readers_on_the_recorded_trace_without_scopes(tmp_path,
                                                      monkeypatch):
    """The recorded trace is of a program with neither scopes nor wait
    spans: the new readers find nothing, the older ones read what
    ``expected.json`` gives."""
    red = trace.reduce_file(TRACE, WANT["devices"])
    run = tmp_path / ".runs" / "cell" / "trace-1"
    run.mkdir(parents=True)
    shutil.copy(TRACE, run / "h.xplane.pb")
    found = scopes.for_reduced
    monkeypatch.setattr(scopes, "for_reduced",
                        lambda r: found(r, tmp_path))
    assert _reader("unscoped_share.decode").read(red, {}) is None
    assert _reader("host_share.decode").read(red, {}) is None
    idle = _reader("idle_share.decode").read(red, {})
    assert idle == pytest.approx(
        100.0 * (1 - WANT["busy_s"] / WANT["window_s"]), rel=1e-9)
    facts = {"conf": json.loads((ROOT / "bench" / "configs" /
                                 "h2o-danube-1.8b.json").read_text()),
             "decode_rows": [[100, 200]], "prefill_lens": [],
             "decode_steps": 4, "occupancy_sum": 3.0, "chips": 1,
             "peak": harness.peaks("TPU v5 lite")}
    assert _reader("paged_decode_roofline.decode").read(red, facts) is None
    assert _reader("decode_occupancy.decode").read(red, facts) == 75.0
    assert _reader("mfu.decode").read(red, facts) > 0


def test_probe_records_the_window_steps(tiny_root):
    import time

    from bench import probe

    out = probe.probe(tiny_root, "tiny.serve", 2**31 + 77, 1.0, False,
                      time.perf_counter(), require_chip=False, cache=False)
    res = out["result"]
    assert res["correct"], res["checks"]
    slowest = out["slowest"]
    assert len(slowest) == min(6, res["notes"]["window_steps"])
    assert slowest[0]["ms"] == pytest.approx(
        res["notes"]["step_ms_slowest"][-1], rel=0.05)
    assert [s["ms"] for s in slowest] == sorted(
        (s["ms"] for s in slowest), reverse=True)
    for s in slowest:
        assert sum(s["phases_ms"].values()) <= s["ms"]
        assert {"gc_s", "gc_gen2", "compiles"} <= set(s)
    assert out["window_compiles"] == 0
