"""The prefill cell's pieces on the CPU: the time-to-first-token samples,
the open loop's arrivals, the four prefill readers, and the check on the
tiny stand-in of ``serve.deepseek.prefill`` (multi-head attention with no
window, sent as an open loop)."""

import json
import time

import numpy as np
import pytest

from bench import counts, harness, trace, workload
from bench.tests.tiny import ROOT, TINY_PREFILL_CONF

SEED = 2**31 + 4242


def _run(root, cell, fault=None, calibrate=False):
    from bench import run as bench_run

    return bench_run.run_cell(root, cell, SEED, 1.0, False,
                              t_start=time.perf_counter(),
                              require_chip=False, fault=fault,
                              calibrate=calibrate, cache=False)


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.prefill"])
def test_ttft_samples_are_first_tokens_in_the_window(tiny_root, cell):
    """Requests answered before the window opens give no sample; every
    request whose first token comes in the window gives one, and it is at
    least as long as the step that emitted that token."""
    c = harness.load_cell(tiny_root, cell)
    driver = harness.load_module(c.driver_path(), "t_driver_")
    eng = driver.build(c, SEED, harness.devices(1, require_chip=False))
    steps = []              # (start, end, uids given a token)
    inner = eng.step

    def step():
        t = time.perf_counter()
        out = inner()
        steps.append((t, time.perf_counter(), [u for u, _ in out]))
        return out
    eng.step = step
    reqs = workload.serve_requests(c.traffic, c.conf["vocab_size"], SEED)
    loop = driver.Loop(eng, reqs, workload.serve_arrivals(c.traffic))
    loop.submit()
    loop.submit()
    while any(r["first"] is None for r in loop.recs.values()):
        loop.step()
    before = set(loop.recs)
    t0 = loop.t0 = loop.origin = time.perf_counter()
    loop.run_until(t0 + 0.5)

    first_step = {}
    for s, e, uids in steps:
        for u in uids:
            first_step.setdefault(u, (s, e))
    in_window = {u for u, (s, _) in first_step.items() if s >= t0}
    assert loop.ttft and before and not before & set(loop.ttft)
    assert set(loop.ttft) == in_window
    for uid, ttft in loop.ttft.items():
        s, e = first_step[uid]
        assert loop.recs[uid]["sent"] <= s
        assert ttft >= e - s
    # an answer sample for each request whose last token comes in the
    # window, no shorter than its time to the first token
    done = [r for r in loop.recs.values() if r.get("done", t0 - 1) >= t0]
    assert len(loop.answer) == len(done)
    assert sorted(loop.answer) == sorted(r["done"] - r["sent"] for r in done)
    assert all(r["done"] - r["sent"] >= loop.ttft[r["req"].uid]
               for r in done if r["req"].uid in loop.ttft)
    # an open loop sends each request at or after its due time
    assert (loop.arrivals is None) == (not loop.late)
    assert all(late >= 0 for late in loop.late)


def test_open_loop_sends_the_same_work_at_the_same_times_for_every_seed():
    tr = json.loads((ROOT / "bench/traffic/prefill_short_answer.json")
                    .read_text())
    a = workload.serve_requests(tr, 102400, SEED)
    b = workload.serve_requests(tr, 102400, SEED + 1)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert a[0].prompt != b[0].prompt
    # no request runs past the model's 4,096 positions
    assert max(len(r.prompt) + r.max_new for r in a) <= tr["max_len"] == 4096
    assert min(len(r.prompt) for r in a) >= tr["prompt"]["lo"]
    times = workload.serve_arrivals(tr)
    assert times == workload.serve_arrivals(tr)
    assert np.all(np.diff(times) > 0)
    rate = tr["arrivals"]["rate_per_s"]
    assert len(times) / times[-1] == pytest.approx(rate, rel=0.15)
    closed = dict(tr, clients=8)
    del closed["arrivals"]
    assert workload.serve_arrivals(closed) is None


def test_decode_cell_result_keys_are_unchanged(tiny_root):
    res = _run(tiny_root, "tiny.serve")
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "notes", "checks"]
    assert set(res["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                   "itl_p95_ms"}
    assert res["correct"], res["checks"]


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                               "t_metric_")


def test_prefill_padding_by_hand():
    read = _reader("prefill_padding.prefill").read
    facts = {"prefill_lens": [3000, 2048, 100],
             "prefill_buckets": [4096, 2048, 128]}
    # (4096 + 2048 + 128) - (3000 + 2048 + 100) = 1124 of 6272 positions
    assert read(None, facts) == pytest.approx(100.0 * 1124 / 6272)
    assert read(None, {"prefill_lens": [], "prefill_buckets": []}) is None


def _one_device(ops, host=()):
    return trace.Reduced([trace.Device("/device:TPU:0", (0, 10**9), ops)],
                         host=list(host))


def test_prefill_roofline_and_mfu_by_hand():
    peak = {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
    facts = {"conf": TINY_PREFILL_CONF, "chips": 1, "peak": peak,
             "prefill_lens": [20], "prefill_buckets": [32],
             "decode_rows": [[20, 21]]}
    red = _one_device([(0, 4 * 10**8, "flash_attention_fwd.7"),
                       (4 * 10**8, 6 * 10**8, "fusion.3")])
    # 20 tokens: 210 visible pairs, 4 heads of 16, 2 layers; q, k, v in
    # and o out at 20 x 4 x 16 bf16 each, per layer
    flops = 4 * 4 * 16 * 210 * 2
    nbytes = 2 * 4 * (20 * 4 * 16 * 2)
    assert counts.prefill_flash_counts(TINY_PREFILL_CONF, 20) == {
        "flops": flops, "bytes": nbytes}
    least = max(flops / 1e6, nbytes / 1e5)
    got = _reader("prefill_attn_roofline.prefill").read(red, facts)
    assert got == pytest.approx(100.0 * least / 0.4)
    assert _reader("prefill_attn_roofline.prefill").read(
        _one_device([(0, 10, "fusion.3")]), facts) is None
    want = (counts.prefill_flops(TINY_PREFILL_CONF, 20)
            + counts.decode_step_flops(TINY_PREFILL_CONF, [20, 21]))
    got = _reader("mfu.prefill").read(red, facts)
    assert got == pytest.approx(100.0 * want / (1.0 * 1e6))
    assert _reader("idle_share.prefill").read(red, facts) == \
        pytest.approx(40.0)
    # the last 0.2 s of the window the loop waits for an arrival: that
    # time counts neither as the engine's nor as idle
    waiting = _one_device(red.devices[0].ops,
                          [(8 * 10**8, 10 * 10**8, trace.IDLE)])
    assert _reader("mfu.prefill").read(waiting, facts) == \
        pytest.approx(100.0 * want / (0.8 * 1e6))
    assert _reader("idle_share.prefill").read(waiting, facts) == \
        pytest.approx(25.0)


def test_prefill_sound_run_is_correct_and_control_fails(tiny_root):
    res = _run(tiny_root, "tiny.prefill", calibrate=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms", "answer_p50_ms"}
    assert res["metrics"]["ttft_p50_ms"]["value"] > 0
    # an answer ends after its first token
    assert res["metrics"]["answer_p50_ms"]["value"] > \
        res["metrics"]["ttft_p50_ms"]["value"]
    notes = res["notes"]
    assert notes["ttft_samples"] > 0 and notes["checked_tokens"] > 0
    assert notes["longest_context"] <= 32
    limit = res["checks"]["served_gap"]["limit"]
    assert notes["readings"]["served_gap"]["control"] > limit


def _alter_first_token(eng):
    """The first token of every request, the prefill's own output, is
    altered where the engine emits it."""
    step = eng.step
    seen = set()

    def altered():
        out = []
        for uid, tok in step():
            if uid not in seen:
                seen.add(uid)
                tok = (tok + 1) % eng.cfg.vocab_size
            out.append((uid, tok))
        return out
    eng.step = altered


def _fp8_control_in_place(eng):
    """Every token the engine emits is replaced by the fp8 reference's
    first choice on the same context."""
    import jax

    from bench import reference

    arch = reference.Arch.from_config(TINY_PREFILL_CONF)
    params = reference.init_params(arch, SEED)
    span = eng.eng.max_len

    @jax.jit
    def first_choice(tokens, pos):
        h = reference.hidden(params, tokens, arch, precision="fp8")
        return reference.logits(params, h[pos][None], arch, "fp8")[0].argmax()

    add, step = eng.add_request, eng.step
    seqs = {}

    def added(req):
        seqs[req.uid] = list(req.tokens)
        return add(req)

    def control():
        out = []
        for uid, _ in step():
            seq = seqs[uid]
            tokens = np.zeros((span,), np.int32)
            tokens[:len(seq)] = seq
            tok = int(first_choice(tokens, len(seq) - 1))
            seq.append(tok)
            out.append((uid, tok))
        return out
    eng.add_request, eng.step = added, control


@pytest.mark.parametrize("fault", [_alter_first_token, _fp8_control_in_place],
                         ids=["altered_first_token", "fp8_control"])
def test_prefill_fault_is_caught(tiny_root, fault):
    res = _run(tiny_root, "tiny.prefill", fault=fault)
    assert res["notes"]["checked_tokens"] > 0
    assert not res["correct"], res["checks"]
