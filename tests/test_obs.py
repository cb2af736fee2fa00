"""Unit tests for repro.obs: registry label semantics, fixed-bucket
histogram quantiles, Prometheus render/parse round-trip, and span
nesting/ordering — including under two engines' interleaved steps on the
single-device mesh (the commlog measured-vs-analytical check needs 8
devices and runs as the ``commlog_c2`` batch in test_system.py)."""

import json

import pytest

from repro import obs


# ---------------------------------------------------------------------------
# counters / gauges: label semantics
# ---------------------------------------------------------------------------

def test_counter_label_series_are_independent():
    reg = obs.Registry()
    c = reg.counter("reqs_total", "requests")
    c.inc(replica="0")
    c.inc(2, replica="1")
    c.inc(replica="0", kind="long")
    assert c.value(replica="0") == 1            # exact-match read
    assert c.value(replica="1") == 2
    assert c.value(replica="0", kind="long") == 1
    assert c.value(replica="2") == 0            # never-touched series
    assert c.sum(replica="0") == 2              # superset match
    assert c.sum() == 4
    assert set(c.series(replica="0")) == {
        (("replica", "0"),), (("kind", "long"), ("replica", "0"))}
    with pytest.raises(ValueError):
        c.inc(-1)
    c.reset(replica="0")                        # drops both replica=0 series
    assert c.sum() == 2


def test_gauge_ops_and_registry_lookup():
    reg = obs.Registry()
    g = reg.gauge("depth", "queue depth")
    g.set(3, q="a")
    g.inc(2, q="a")
    g.dec(q="a")
    g.max(10, q="a")
    g.max(4, q="a")                              # lower value: no-op
    assert g.value(q="a") == 10
    assert reg.gauge("depth") is g               # get-or-create
    assert reg.value("depth", q="a") == 10
    with pytest.raises(ValueError):
        reg.counter("depth")                     # kind mismatch


def test_scope_contextvar_nesting():
    assert obs.current_scope() == "global"
    with obs.scope("outer"):
        assert obs.current_scope() == "outer"
        with obs.scope("inner"):
            assert obs.current_scope() == "inner"
        assert obs.current_scope() == "outer"
    assert obs.current_scope() == "global"


# ---------------------------------------------------------------------------
# histograms: fixed-bucket quantiles
# ---------------------------------------------------------------------------

def test_histogram_quantiles_on_fixed_buckets():
    reg = obs.Registry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 0.2, 0.4))
    for _ in range(10):
        h.observe(0.15)
    # all mass in (0.1, 0.2]: linear interpolation inside that bucket
    assert h.quantile(0.5) == pytest.approx(0.15)
    assert h.quantile(0.99) == pytest.approx(0.199)
    h.reset()
    for _ in range(5):
        h.observe(0.05)                          # (0, 0.1]
    for _ in range(5):
        h.observe(0.3)                           # (0.2, 0.4]
    assert h.count() == 10
    assert h.bucket_counts() == [5, 0, 5, 0]
    assert h.quantile(0.5) == pytest.approx(0.1)   # exactly at bucket edge
    assert h.quantile(0.95) == pytest.approx(0.38)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_overflow_clamps_to_last_finite_bound():
    reg = obs.Registry()
    h = reg.histogram("lat", "latency", buckets=(0.1, 0.2, 0.4))
    h.observe(100.0)
    assert h.bucket_counts() == [0, 0, 0, 1]
    assert h.quantile(0.5) == pytest.approx(0.4)   # +Inf bucket lower bound


def test_histogram_labels_aggregate_like_counters():
    reg = obs.Registry()
    h = reg.histogram("ttft", "", buckets=obs.TTFT_BUCKETS)
    h.observe(0.02, replica="0")
    h.observe(0.02, replica="1")
    assert h.count(replica="0") == 1
    assert h.count() == 2                        # no filter: all replicas
    assert h.quantile(0.5) == h.quantile(0.5, replica="0")
    with pytest.raises(ValueError):
        reg.histogram("ttft", buckets=(1.0, 2.0))  # conflicting buckets


# ---------------------------------------------------------------------------
# Prometheus exposition round-trip
# ---------------------------------------------------------------------------

def test_prometheus_render_parse_round_trip():
    reg = obs.Registry()
    reg.counter("a_total", "a help").inc(3, entry='we"ird\nname',
                                         path="a\\b")
    reg.gauge("b").set(2.5, x="1")
    h = reg.histogram("c_seconds", "hist", buckets=(0.5, 1.0))
    h.observe(0.3)
    h.observe(2.0)
    text = reg.render_prometheus()
    assert "# TYPE a_total counter" in text
    assert "# TYPE c_seconds histogram" in text
    parsed = obs.parse_prometheus(text)
    key = (("entry", 'we"ird\nname'), ("path", "a\\b"))
    assert parsed[("a_total", key)] == 3
    assert parsed[("b", (("x", "1"),))] == 2.5
    # histogram samples: cumulative buckets + sum + count
    assert parsed[("c_seconds_bucket", (("le", "0.5"),))] == 1
    assert parsed[("c_seconds_bucket", (("le", "1"),))] == 1
    assert parsed[("c_seconds_bucket", (("le", "+Inf"),))] == 2
    assert parsed[("c_seconds_sum", ())] == pytest.approx(2.3)
    assert parsed[("c_seconds_count", ())] == 2


def test_registry_json_dump(tmp_path):
    reg = obs.Registry()
    reg.counter("a_total").inc(7, k="v")
    p = tmp_path / "m.json"
    reg.dump(str(p), fmt="json")
    d = json.loads(p.read_text())
    assert d["a_total"]["kind"] == "counter"
    assert d["a_total"]["series"] == [{"labels": {"k": "v"}, "value": 7.0}]


# ---------------------------------------------------------------------------
# tracer: spans, nesting, async pairs, disabled no-op
# ---------------------------------------------------------------------------

def test_tracer_span_nesting_and_ordering():
    tr = obs.Tracer(enabled=True)
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t", k=1):
            pass
    with tr.span("later", cat="t"):
        pass
    ev = {e["name"]: e for e in tr.events()}
    inner, outer, later = ev["inner"], ev["outer"], ev["later"]
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["args"]["k"] == 1
    # containment: inner lies within outer; later starts after outer ends
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["ts"] + outer["dur"] <= later["ts"]
    body = tr.chrome_trace()
    assert json.loads(json.dumps(body))["traceEvents"] == tr.events()


def test_tracer_async_pairs_and_instant():
    # an instant inside the lifecycle is an empty sync span
    tr = obs.Tracer(enabled=True)
    sid = tr.async_begin("request", uid="r0")
    with tr.span("tick"):
        pass
    tr.async_end("request", sid, tokens=3)
    phs = [e["ph"] for e in tr.events()]
    assert phs == ["b", "X", "e"]
    b, tick, e = tr.events()
    assert b["id"] == e["id"] == sid
    assert b["ts"] <= tick["ts"] <= tick["ts"] + tick["dur"] <= e["ts"]


def test_disabled_tracer_is_a_noop():
    tr = obs.NULL_TRACER
    with tr.span("x"):
        pass
    assert tr.async_begin("r") is None
    tr.async_end("r", None)
    tr.async_end("r", "s1")
    assert tr.events() == []


# ---------------------------------------------------------------------------
# spans under interleaved engine steps (single-device mesh)
# ---------------------------------------------------------------------------

def test_span_nesting_under_interleaved_engine_steps():
    import numpy as np

    from repro.engine import EngineConfig, Request, build_engine

    tracer = obs.Tracer(enabled=True)
    ecfg = EngineConfig(max_slots=2, page_size=4, pages_per_shard=32,
                        max_len=64)
    eng_a = build_engine("h2o-danube-1.8b", smoke=True, c=1, data=1,
                         eng=ecfg, tracer=tracer)
    eng_b = build_engine("h2o-danube-1.8b", smoke=True, c=1, data=1,
                         eng=ecfg, params=eng_a.params, tracer=tracer)
    rng = np.random.default_rng(3)
    vocab = eng_a.cfg.vocab_size
    for i, eng in enumerate((eng_a, eng_b)):
        for j in range(2):
            eng.add_request(Request(
                uid=f"e{i}r{j}", tokens=rng.integers(0, vocab, 5).tolist(),
                max_new_tokens=3))
    while not (eng_a.idle() and eng_b.idle()):   # interleave the engines
        for eng in (eng_a, eng_b):
            if not eng.idle():
                eng.step()

    events = tracer.events()
    steps = [e for e in events if e["name"] == "engine/step"]
    inner = [e for e in events
             if e["name"] in ("engine/prefill", "engine/decode",
                              "engine/prefill_chunk")]
    assert {e["args"]["scope"] for e in steps} == \
        {eng_a.obs_scope, eng_b.obs_scope}
    # every inner phase span is contained in exactly one step span
    for e in inner:
        owners = [s for s in steps
                  if s["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= s["ts"] + s["dur"]]
        assert len(owners) == 1, (e["name"], len(owners))
    # step spans never overlap (one thread drives both engines), and the
    # interleave shows up as alternating scopes in ts order
    steps.sort(key=lambda s: s["ts"])
    for prev, cur in zip(steps, steps[1:]):
        assert prev["ts"] + prev["dur"] <= cur["ts"]
    scopes = [s["args"]["scope"] for s in steps]
    assert any(a != b for a, b in zip(scopes, scopes[1:]))
    # request lifecycle: one async begin/end pair per request, b before e
    asyncs = [e for e in events if e["ph"] in ("b", "e")]
    by_id = {}
    for e in asyncs:
        by_id.setdefault(e["id"], []).append(e)
    uids = set()
    assert len(by_id) == 4
    for pair in by_id.values():
        assert [e["ph"] for e in pair] == ["b", "e"]
        assert pair[0]["ts"] <= pair[1]["ts"]
        uids.add(pair[0]["args"]["uid"])
    assert uids == {"e0r0", "e0r1", "e1r0", "e1r1"}


# ---------------------------------------------------------------------------
# host phases of an engine step, and the process's GC / compile counters
# ---------------------------------------------------------------------------

def _phase_engine(tracer=None):
    from repro.engine import EngineConfig, build_engine

    ecfg = EngineConfig(max_slots=2, page_size=4, pages_per_shard=32,
                        max_len=64)
    return build_engine("h2o-danube-1.8b", smoke=True, c=1, data=1,
                        eng=ecfg, tracer=tracer)


def _requests(eng, n, new=6):
    import numpy as np

    from repro.engine import Request

    rng = np.random.default_rng(5)
    for j in range(n):
        eng.add_request(Request(
            uid=f"r{j}", tokens=rng.integers(0, eng.cfg.vocab_size,
                                             5 + j).tolist(),
            max_new_tokens=new))


def _inside(e, outer):
    return outer["ts"] <= e["ts"] and \
        e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]


def test_engine_phase_spans_nest():
    tracer = obs.Tracer(enabled=True)
    eng = _phase_engine(tracer)
    _requests(eng, 3)
    eng.run()
    events = [e for e in tracer.events() if e["ph"] == "X"]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    assert {"engine/admit", "engine/decode_inputs", "engine/decode_launch",
            "engine/decode_wait", "engine/prefill_wait",
            "engine/emit"} <= set(by)
    for name, parent in (("engine/decode_inputs", "engine/decode"),
                         ("engine/decode_launch", "engine/decode"),
                         ("engine/decode_wait", "engine/decode"),
                         ("engine/prefill_wait", "engine/prefill"),
                         ("engine/admit", "engine/step"),
                         ("engine/emit", "engine/step")):
        for e in by[name]:
            assert sum(_inside(e, p) for p in by[parent]) == 1, name
    # the three decode phases run in order inside each decode span
    for d in by["engine/decode"]:
        kids = sorted((e for n in ("engine/decode_inputs",
                                   "engine/decode_launch",
                                   "engine/decode_wait")
                       for e in by[n] if _inside(e, d)),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in kids] == [
            "engine/decode_inputs", "engine/decode_launch",
            "engine/decode_wait"]


def test_engine_phase_counters_cover_the_step():
    import time

    from repro.engine.engine import PHASES

    eng = _phase_engine()
    _requests(eng, 2, new=4)
    eng.run()                                   # compile every shape first
    eng.reset()
    _requests(eng, 2, new=24)
    wall, phases = 0.0, dict.fromkeys(PHASES, 0.0)
    while not eng.idle():
        t = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t
        assert set(eng.last_step_phases) == set(PHASES)
        for k, v in eng.last_step_phases.items():
            phases[k] += v
    assert sum(phases.values()) == pytest.approx(wall, rel=0.05)
    assert phases["decode_wait"] > 0 and phases["prefill_wait"] > 0
    # folded into the engine's registry once per step, reset with it
    assert eng.metrics.phase_seconds() == pytest.approx(phases)
    text = eng.registry.render_prometheus()
    assert 'engine_phase_seconds_total{phase="decode_wait"}' in text
    eng.reset()
    assert sum(eng.metrics.phase_seconds().values()) == 0.0


def test_gc_collect_raises_the_gc_counters():
    import gc

    watch = obs.watch_host()
    before = watch.snapshot()
    gc.collect()
    after = watch.snapshot()
    assert after["gc_gen2"] >= before["gc_gen2"] + 1
    assert after["gc_s"] > before["gc_s"]
    watch.fold()
    reg = obs.global_registry()
    assert reg.value("host_gc_collections_total", generation=2) == \
        after["gc_gen2"]
    assert reg.value("host_gc_seconds_total") == after["gc_s"]


def test_a_new_decode_width_raises_the_compile_counters():
    import numpy as np

    eng = _phase_engine()
    _requests(eng, 1, new=2)
    eng.run()
    before = eng.host.snapshot()
    width = 2 * eng.scheduler.table_width
    B = eng.eng.max_slots
    fn = eng._decode_fn(width, False)
    fn(eng.params, eng.pools, np.zeros((B, 1), np.int32),
       np.zeros((B,), np.int32), np.zeros((B, 1, width), np.int32),
       np.zeros((B,), np.float32), np.zeros((B,), np.int32),
       np.ones((B,), np.float32), np.zeros((B, 2), np.uint32),
       np.zeros((B,), bool))
    after = eng.host.snapshot()
    assert after["compiles"] > before["compiles"]
    assert after["compile_s"] > before["compile_s"]


def test_host_spans_land_on_the_profiler_clock(tmp_path):
    """With an annotating tracer, a GC pause and a compile are host spans
    in the profiler's own trace, beside the engine's."""
    import gc
    import pathlib
    import sys

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from bench import scopes

    tracer = obs.Tracer(enabled=True, annotate=True)
    watch = obs.watch_host(tracer)
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench/window"):
            gc.collect()
            jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 3)))
        jax.profiler.stop_trace()
    finally:
        watch.tracer = obs.NULL_TRACER
    [path] = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    tl = scopes.read(path, 0)
    names = {n for s, e, n in tl.host
             if tl.window[0] <= s and e <= tl.window[1]}
    assert {"host/gc", "host/compile"} <= names
