"""The training cell on the CPU: its inputs, its reference, the numbers it
compares, its trace readers, and whole runs of a tiny copy on four host
devices, sound and with the timed path broken underneath (the
harness's look for a chip skipped)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.tests.tiny import ROOT, TINY_CONF, TINY_TRAIN

SEED = 2**31 + 77


# -- inputs -------------------------------------------------------------------

def test_batches_are_the_program_synthetic_stream():
    """The benchmark's batches, laid out as the plan asks, are those of
    the program's own synthetic source, step for step."""
    from bench import harness, train_workload
    from repro.configs.base import ShapeConfig
    from repro.core import zigzag
    from repro.data.pipeline import SyntheticLM

    cfg = harness.model_config(TINY_CONF)
    shape = ShapeConfig("t", seq_len=64, global_batch=2, kind="train")
    prog = SyntheticLM(cfg, shape, seed=SEED, seq_scheme="zigzag",
                       sp_size=4)
    perm = zigzag.make_positions(64, 4, "zigzag").reshape(-1)
    ours = train_workload.Source(SEED, 2, 64, cfg.vocab_size, perm)
    for step in (0, 1, 5):
        a, b = prog.get_batch(step), ours.get_batch(step)
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(ours.get_batch(0)["tokens"],
                              ours.get_batch(1)["tokens"])


# -- the reference ------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("fault", ["none", "half_batch", "local_chunk"])
def test_layer_by_layer_gradient_is_jax_grad_of_the_forward(dtype, fault):
    """The reference's loss and gradient, taken one layer at a time, are
    ``jax.grad`` of its own whole forward pass, and that forward pass is
    ``reference.py``'s decoder."""
    import jax
    import jax.numpy as jnp

    from bench import reference, reference_train as rt, train_workload

    a = reference.Arch.from_config(dict(TINY_CONF, torch_dtype=dtype))
    b = train_workload.batch(SEED, 0, 2, 64, a.vocab_size)
    mesh = rt.mesh_of(jax.devices()[:1])
    f = {"none": rt.NO_FAULT, "half_batch": rt.Fault(half_batch=True),
         "local_chunk": rt.Fault(local_chunk=8)}[fault]
    kw = dict(precision="f32", qblock=16, mesh=mesh, fault=f)
    params = reference.init_params(a, SEED)
    value, grads = jax.jit(lambda p: rt.value_and_grads(
        p, b["tokens"], b["labels"], a, **kw))(params)
    want, want_g = jax.jit(jax.value_and_grad(lambda p: rt.loss(
        p, b["tokens"], b["labels"], a, **kw)))(rt._f32(params))
    assert abs(float(value) - float(want)) <= 1e-5 * abs(float(want))
    assert set(grads) == set(want_g) == set(params)
    for n in grads:
        scale = float(jnp.max(jnp.abs(want_g[n])))
        assert float(jnp.max(jnp.abs(grads[n] - want_g[n]))) <= 1e-5 * scale
    if fault == "none":
        h = reference.hidden(rt._f32(params), b["tokens"][0], a, qblock=16)
        lg = reference.logits(rt._f32(params), h, a)
        ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, b["labels"][0][:, None], -1)[:, 0]
        one = rt.loss(params, b["tokens"][:1], b["labels"][:1], a, **kw)
        assert abs(float(jnp.mean(ce)) - float(one)) <= 1e-5 * float(one)


def _reading(losses, grad, change):
    return {"losses": losses, "grad": grad, "change": change}


def test_gaps_by_the_worst_weight():
    from bench.reference_train import gaps

    ref = _reading([10.0, 9.0], {"a": 1.0, "b": 2.0, "c": 4.0, "s": 1e-6},
                   {"a": 0.1, "b": 0.2, "c": 0.4, "s": 0.0})
    prog = _reading([10.01, 8.97], {"a": 1.1, "b": 2.0, "c": 4.0, "s": 0.5},
                    {"a": 0.1, "b": 0.3, "c": 0.4, "s": 0.9})
    g = gaps(prog, ref)
    assert g["train_loss_gap"] == pytest.approx(0.03)
    # "s" is far under the median weight's gradient (1.5): its gap is
    # measured against the median, and its change is not compared
    assert g["grad_leaf_gap"] == pytest.approx((0.5 - 1e-6) / 1.5)
    assert g["change_leaf_gap"] == pytest.approx(0.1 / 0.2)


def test_gaps_of_a_missing_reading_are_infinite():
    from bench.reference_train import gaps

    ref = _reading([10.0, 9.0], {"a": 1.0}, {"a": 0.1})
    g = gaps(_reading([10.0], {}, {}), ref)
    assert all(v == float("inf") for v in g.values())


# -- trace readers ------------------------------------------------------------

def _hlo(name, opcode):
    return (f"%{name} = bf16[8,128]{{1,0:T(8,128)(2,1)}} "
            f"{opcode}(bf16[8,128] %x)")


@pytest.mark.parametrize("text,collective", [
    (_hlo("psum.3", "all-reduce"), True),
    (_hlo("reduce_scatter.2", "reduce-scatter"), True),
    (_hlo("all-gather.91", "all-gather"), True),
    ("%collective-permute-start.1 = (bf16[1,4096]{1,0:T(8,128)(2,1)S(1)}, "
     "u32[]{:S(2)}) collective-permute-start(bf16[1,4096] %k)", True),
    (_hlo("collective-permute-done.1", "collective-permute-done"), True),
    (_hlo("fusion.150", "fusion") + ", calls=%all-reduce-like", False),
    (_hlo("flash_attention_fwd.10", "custom-call"), False),
    (_hlo("copy-done", "copy-done"), False),
])
def test_collectives_found_by_opcode(text, collective):
    from bench import overlap

    assert overlap.is_collective(text) is collective


@pytest.mark.parametrize("ops,want", [
    # one after another on the device's line
    ([(0, 10, _hlo("ag.1", "all-gather")), (10, 15, _hlo("f.1", "fusion")),
      (15, 18, _hlo("ps.1", "all-reduce"))], 13),
    # inside a loop that spans them: the loop's own time is not exposed
    ([(0, 100, _hlo("while.1", "while")),
      (10, 20, _hlo("cp.1", "collective-permute-done")),
      (20, 30, _hlo("f.2", "fusion"))], 10),
    # an asynchronous transfer hidden behind compute: only its start and
    # done events count
    ([(0, 1, _hlo("cps.1", "collective-permute-start")),
      (1, 50, _hlo("f.3", "fusion")),
      (50, 52, _hlo("cpd.1", "collective-permute-done"))], 3),
    # a collective nested in a fusion counts, the fusion's rest does not
    ([(0, 50, _hlo("f.4", "fusion")), (10, 20, _hlo("rs.1",
                                                   "reduce-scatter"))], 10),
    ([(0, 50, _hlo("f.5", "fusion"))], 0),
])
def test_exposed_collective_time(ops, want):
    from bench import overlap

    assert overlap.exposed_ns(ops) == want


def _readers():
    from bench import harness

    return {name: harness.load_module(
        ROOT / "bench" / "metrics" / f"{name}.py", "t_train_")
        for name in ("mfu.train", "flash_fwd_roofline.train",
                     "flash_bwd_roofline.train", "idle_share.train")}


def test_train_readers_on_a_hand_made_trace():
    from bench import trace

    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    w = (0, 2_000_000_000)   # a 2 s window
    d0 = trace.Device("0", w, [(0, 500_000_000, "flash_attention_fwd.4"),
                               (600_000_000, 1_600_000_000,
                                "flash_attention_bwd.7")])
    d1 = trace.Device("1", w, [(0, 250_000_000, "flash_attention_fwd.4"),
                               (250_000_000, 1_750_000_000, "fusion.3")])
    red = trace.Reduced([d0, d1], [])
    facts = {"kind": "train", "chips": 2, "steps": 4, "step_flops": 50.0,
             "peak": peak,
             "flash_per_chip": {"fwd": {"flops": 10.0, "bytes": 1.0},
                                "bwd": {"flops": 5.0, "bytes": 3.0}}}
    r = _readers()
    # 4 steps of 50 operations over 2 s, 2 chips of 100 per second
    assert r["mfu.train"].read(red, facts) == pytest.approx(50.0)
    # least 0.1 s a step (compute-bound), 4 steps, 0.5 s on the busiest
    assert r["flash_fwd_roofline.train"].read(red, facts) == \
        pytest.approx(80.0)
    # least 0.3 s a step (memory-bound), 4 steps, 1 s on device 0
    assert r["flash_bwd_roofline.train"].read(red, facts) == \
        pytest.approx(120.0)
    # device 0 is busy 1.5 of 2 s, device 1 1.75 s
    assert r["idle_share.train"].read(red, facts) == pytest.approx(25.0)
    for reader in r.values():
        assert reader.read(red, {"kind": "serve"}) is None
    no_kernel = trace.Reduced([trace.Device("0", w, [(0, 5, "fusion.1")])],
                              [])
    assert r["flash_fwd_roofline.train"].read(no_kernel, facts) is None


# -- whole runs on four host devices ------------------------------------------

SCENARIOS = ["sound", "fp8_control", "unchanged_state", "half_batch",
             "exchange_left_out"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Each scenario of ``train_faults.py`` once, in one process on four
    host devices."""
    from bench.tests.tiny import make_tiny_root

    root = make_tiny_root(tmp_path_factory.mktemp("tiny"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "train_faults.py"),
         str(root), *SCENARIOS], cwd=root, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-5000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            out[rec["scenario"]] = rec
    assert set(out) == set(SCENARIOS)
    return out


def test_sound_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert r["attempted"] == r["window_steps"] > 0
    assert r["device"]["count"] == 4
    plan = r["plan"]
    assert (plan["scheme"], plan["c"], plan["r"], plan["sp_size"],
            plan["seq_scheme"]) == ("startrail", 2, 1, 4, "zigzag")
    assert len(r["checked_losses"]) == TINY_TRAIN["check"]["steps"]
    # the control's readings, beside the program's, fail a limit
    ctl = r["readings"]["control"]
    assert any(ctl[k] > r["checks"][k]["limit"] for k in ctl)


@pytest.mark.parametrize("scenario", SCENARIOS[1:])
def test_broken_step_is_caught(runs, scenario):
    r = runs[scenario]
    assert not r["correct"], r["checks"]


def test_train_cell_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.danube.p4.startrail", "--seed", "2147483659", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert '"metrics"' not in out.stdout
