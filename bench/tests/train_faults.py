"""The tiny training cell driven on four CPU devices, sound and with the
timed path broken underneath. Run as a script under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``:

    python bench/tests/train_faults.py <tiny root> <scenario> ...

prints one JSON line per scenario: its ``correct``, ``checks``,
``metrics`` and the notes the tests read.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2**31 + 4242


def fp8_control(t):
    """The control: the fp8 reference's step put in the program's place,
    on the program's own state and batches (laid out back into order)."""
    import jax
    import numpy as np

    from bench import reference, reference_train

    cell = t.cell
    arch = reference.Arch.from_config(cell.conf)
    devs = jax.devices()[:cell.chips]
    mesh = reference_train.mesh_of(devs)
    sh = reference_train.shardings(arch, mesh)
    step = reference_train.make_step(
        arch, reference_train.Adam(**cell.traffic["optimizer"]), mesh,
        precision="fp8", qblock=int(cell.traffic["check"]["qblock"]))
    inv = np.argsort(t.perm)

    def control(params, opt, batch):
        put = lambda tree: {n: jax.device_put(x, sh[n])  # noqa: E731
                            for n, x in reference.flatten(tree).items()}
        tokens = np.asarray(batch["tokens"])[:, inv]
        labels = np.asarray(batch["labels"])[:, inv]
        p, mu, nu, count, loss, _, gnorm = step(
            put(params), put(opt["mu"]), put(opt["nu"]), opt["step"],
            tokens, labels)
        return (reference.nest(p), {"mu": reference.nest(mu),
                                    "nu": reference.nest(nu),
                                    "step": count},
                {"loss": loss, "grad_norm": gnorm})

    t.step_fn = control


def unchanged_state(t):
    """Each step computes its loss and returns the state it was given."""
    import jax
    import jax.numpy as jnp

    step = t.step_fn

    def same(params, opt, batch):
        copy = lambda tree: jax.tree.map(jnp.copy, tree)  # noqa: E731
        _, _, m = step(copy(params), copy(opt), batch)
        return params, opt, m

    t.step_fn = same


def half_batch(t):
    """The loss is the mean over the first half of each device's tokens;
    the rest are left out."""
    import jax.numpy as jnp

    from repro.models import blocks

    orig = blocks.lm_head_logits_and_loss

    def half(rt, params, x, labels, cfg, mask=None):
        n = labels.shape[1]
        keep = (jnp.arange(n) < n // 2).astype(jnp.float32)
        return orig(rt, params, x, labels, cfg,
                    mask=jnp.broadcast_to(keep, labels.shape))

    blocks.lm_head_logits_and_loss = half


def exchange_left_out(t):
    """StarTrail's initial K/V placement leaves every block where it is:
    no device receives another team's keys and values."""
    from repro.core import topology

    topology.StarTrailTopology.init_placement_permutation = (
        lambda self: [(i, i) for i in range(self.sp_size)])


SCENARIOS = {"sound": None, "fp8_control": fp8_control,
             "unchanged_state": unchanged_state, "half_batch": half_batch,
             "exchange_left_out": exchange_left_out}


def main(argv) -> int:
    import jax

    from bench import run as bench_run
    from repro.core import startrail, topology
    from repro.models import blocks

    placement = topology.StarTrailTopology.init_placement_permutation
    head = blocks.lm_head_logits_and_loss
    root, names = pathlib.Path(argv[0]), argv[1:]
    for name in names:
        # undo the last scenario's fault, and forget what was traced with it
        topology.StarTrailTopology.init_placement_permutation = placement
        blocks.lm_head_logits_and_loss = head
        startrail._make_attention.cache_clear()
        jax.clear_caches()
        fault = SCENARIOS[name]
        res = bench_run.run_cell(root, "tiny.train", SEED, 0.3, False,
                                 t_start=time.perf_counter(),
                                 require_chip=False, fault=fault,
                                 calibrate=name == "sound", cache=False)
        notes = res["notes"]
        print(json.dumps({
            "scenario": name, "correct": res["correct"],
            "checks": res["checks"], "metrics": res["metrics"],
            "attempted": res["attempted"], "device": res["device"],
            "plan": notes["plan"], "readings": notes.get("readings"),
            "window_steps": notes["window_steps"],
            "checked_losses": notes["checked_losses"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
