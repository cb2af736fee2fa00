"""Plain reference of the benchmark's training steps.

The decoder is ``bench/reference.py``'s, written out again as one layer
function so that its gradient fits on the cell's chips: the forward pass
keeps only each layer's input (split along the sequence over the chips),
the backward pass takes ``jax.vjp`` of one layer at a time, recomputing
it, and attention runs over blocks of query rows, each block recomputed
in turn. Matmuls run in float32 at the highest precision
(``reference._mm``); ``precision="fp8"`` is the control: every matmul
operand rounded to float8 e4m3 with one scale per tensor, in the
backward pass too (the gradient arriving at a matmul is rounded so
before the two products that take it; the rounding of the forward
operands passes gradients straight through).

A step is the loss (the mean cross-entropy over every token), the
gradient of every weight in float32, and AdamW as the configuration's
optimizer states it: the global norm clipped, bias-corrected moments in
float32, decoupled weight decay, the weights rounded back to their
stored dtype. The weights are the benchmark's (``reference.init_params``,
made again from the seed); the weights and moments are split over the
chips by heads, feed-forward columns and vocabulary rows, and the
compiler places the exchanges. Nothing of the program is imported.

``run`` follows the program's first steps on the same inputs and returns
what the check compares: each step's loss, each weight's gradient norm at
the first step, and each weight's change over all the steps.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import reference

#: the mesh axis the reference splits its weights over
AXIS = "m"
#: which dimension of each weight is split over the chips (where it divides)
SPLIT = {"embed/table": 0, "lm_head/table": 0,
         "stack/sub0/mixer/wq": 2, "stack/sub0/mixer/wk": 2,
         "stack/sub0/mixer/wv": 2, "stack/sub0/mixer/wo": 1,
         "stack/sub0/mlp/w1": 2, "stack/sub0/mlp/w3": 2,
         "stack/sub0/mlp/w2": 1}


@dataclasses.dataclass(frozen=True)
class Adam:
    """AdamW as a traffic file's ``optimizer`` states it."""

    learning_rate: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: Optional[float]
    warmup_steps: int
    decay_steps: int
    min_lr_ratio: float
    state_dtype: str

    def lr(self, step):
        """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
        import jax.numpy as jnp

        s = step.astype(jnp.float32)
        warm = jnp.minimum(s / max(self.warmup_steps, 1), 1.0)
        t = jnp.clip((s - self.warmup_steps)
                     / max(self.decay_steps - self.warmup_steps, 1), 0.0, 1.0)
        cos = self.min_lr_ratio + (1 - self.min_lr_ratio) * 0.5 * (
            1 + jnp.cos(jnp.pi * t))
        return self.learning_rate * warm * cos


@dataclasses.dataclass(frozen=True)
class Fault:
    """A fault planted in the reference put in the program's place, to
    read what the check makes of it: the loss over only the first half of
    each row's tokens, or attention only to keys in the query's own chunk
    of ``local_chunk`` tokens (a device that never receives the others'
    keys and values)."""

    half_batch: bool = False
    local_chunk: Optional[int] = None


NO_FAULT = Fault()


def mesh_of(devices):
    import jax

    return jax.sharding.Mesh(np.asarray(devices), (AXIS,))


def shardings(a: reference.Arch, mesh) -> Dict:
    """name -> NamedSharding: each weight split over the chips along its
    ``SPLIT`` dimension where that divides, else held whole on each."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    out = {}
    for name, (shape, _, _) in reference.param_layout(a).items():
        dim = SPLIT.get(name)
        spec = [None] * len(shape)
        if dim is not None and shape[dim] % n == 0:
            spec[dim] = AXIS
        out[name] = NamedSharding(mesh, P(*spec))
    return out


def _fp8_operand():
    """Rounds to fp8 going forward; passes the gradient straight back."""
    import jax

    @jax.custom_vjp
    def q(x):
        return reference._quantize_fp8(x)

    q.defvjp(lambda x: (reference._quantize_fp8(x), None),
             lambda _, g: (g,))
    return q


def _fp8_gradient():
    """The identity going forward; rounds the gradient to fp8 coming
    back."""
    import jax

    @jax.custom_vjp
    def q(y):
        return y

    q.defvjp(lambda y: (y, None),
             lambda _, g: (reference._quantize_fp8(g),))
    return q


def _mm(spec: str, x, w, precision: str):
    """``reference._mm``; for the fp8 control, fp8 operands in the
    backward pass as well."""
    import jax
    import jax.numpy as jnp

    if precision != "fp8":
        return reference._mm(spec, x, w, precision)
    q = _fp8_operand()
    y = jnp.einsum(spec, q(x.astype(jnp.float32)), q(w.astype(jnp.float32)),
                   precision=jax.lax.Precision.HIGHEST)
    return _fp8_gradient()(y)


def _constrain(x, mesh, *spec):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def attention(q, k, v, a: reference.Arch, qblock: int, precision: str,
              local_chunk: Optional[int] = None):
    """Causal (windowed) attention over blocks of query rows, as
    ``reference._attention``, with each block rematerialised in the
    backward pass. q: (S, Hq, D), k, v: (S, Hkv, D) -> (S, Hq, D)."""
    import jax
    import jax.numpy as jnp

    S, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qblock = min(qblock, S)
    if S % qblock:
        raise ValueError(f"sequence {S} is not a multiple of {qblock}")
    span = S if a.window is None else min(S, a.window + qblock)
    qg = q.reshape(S, hkv, g, hd)

    @jax.checkpoint
    def block(i, qg, k, v):
        q0 = i * qblock
        k0 = jnp.clip(q0 + qblock - span, 0, S - span)
        qb = jax.lax.dynamic_slice_in_dim(qg, q0, qblock, 0)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
        s = _mm("qngd,knd->ngqk", qb, kb, precision) * hd ** -0.5
        pq = q0 + jnp.arange(qblock)[:, None]
        pk = k0 + jnp.arange(span)[None, :]
        vis = pk <= pq
        if a.window is not None:
            vis &= (pq - pk) < a.window
        if local_chunk is not None:
            vis &= (pq // local_chunk) == (pk // local_chunk)
        s = jnp.where(vis, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("ngqk,knd->qngd", p, vb, precision)
        return o.reshape(qblock, hq, hd)

    out = jax.lax.map(lambda i: block(i, qg, k, v), jnp.arange(S // qblock))
    return out.reshape(S, hq, hd)


def _layer(p, x, a: reference.Arch, precision: str, qblock: int, mesh,
           fault: Fault):
    """One decoder layer on one sequence (S, d), float32. The input comes
    split along the sequence and goes out so; inside it is whole."""
    import jax

    mm = _mm
    x = _constrain(x, mesh, None, None)
    h = reference._rmsnorm(x, p["mixer/norm/scale"], a.norm_eps)
    q = mm("sd,dhk->shk", h, p["mixer/wq"], precision)
    k = mm("sd,dhk->shk", h, p["mixer/wk"], precision)
    v = mm("sd,dhk->shk", h, p["mixer/wv"], precision)
    q, k = reference._rope(q, a.rope_theta), reference._rope(k, a.rope_theta)
    o = attention(q, k, v, a, qblock, precision, fault.local_chunk)
    x = x + mm("shk,hkd->sd", o, p["mixer/wo"], precision)
    h = reference._rmsnorm(x, p["mlp/norm/scale"], a.norm_eps)
    u = mm("sd,df->sf", h, p["mlp/w1"], precision)
    gt = mm("sd,df->sf", h, p["mlp/w3"], precision)
    x = x + mm("sf,fd->sd", jax.nn.silu(u) * gt, p["mlp/w2"], precision)
    return _split_rows(x, mesh)


def _split_rows(x, mesh):
    """x (S, d) split along the sequence over the chips, where it divides."""
    return _constrain(x, mesh, AXIS if x.shape[0] % mesh.devices.size == 0
                      else None, None)


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def _stack(params: Dict) -> Dict:
    """The per-layer weights, stacked over the layers, by their names
    inside a layer."""
    pre = "stack/sub0/"
    return {n[len(pre):]: w for n, w in params.items() if n.startswith(pre)}


def _kept(fault: Fault, n: int):
    """Which of a row's ``n`` token losses count (1.0) and which not."""
    import jax.numpy as jnp

    if fault.half_batch:
        return (jnp.arange(n) < n // 2).astype(jnp.float32)
    return jnp.ones((n,), jnp.float32)


def _head_loss(x, final_scale, table, labels, a: reference.Arch,
               precision: str, keep):
    """The sum of the kept token losses of one row: final norm, output
    head, cross-entropy."""
    import jax
    import jax.numpy as jnp

    h = reference._rmsnorm(x, final_scale, a.norm_eps)
    lg = _mm("sd,vd->sv", h, table, precision)[:, :a.vocab_size]
    ce = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * keep)


def loss(params, tokens, labels, a: reference.Arch, *, precision: str,
         qblock: int, mesh, fault: Fault = NO_FAULT):
    """The mean cross-entropy of next-token prediction over every token of
    every row, as one forward pass (``value_and_grads`` is its gradient).
    tokens, labels: (B, S) int32."""
    import jax
    import jax.numpy as jnp

    params = _f32(params)
    stacked = _stack(params)
    total, count = 0.0, 0.0
    for row in range(tokens.shape[0]):
        x = _split_rows(params["embed/table"][tokens[row]], mesh)
        x, _ = jax.lax.scan(
            lambda x, p: (_layer(p, x, a, precision, qblock, mesh, fault),
                          None), x, stacked)
        keep = _kept(fault, x.shape[0])
        total = total + _head_loss(x, params["final_norm/scale"],
                                   reference.head_table(params), labels[row],
                                   a, precision, keep)
        count = count + jnp.sum(keep)
    return total / count


def value_and_grads(params, tokens, labels, a: reference.Arch, *,
                    precision: str, qblock: int, mesh,
                    fault: Fault = NO_FAULT):
    """``loss`` and its float32 gradient for every weight, layer by layer:
    the forward pass keeps each layer's input (split along the sequence
    over the chips), and the backward pass runs ``jax.vjp`` of one layer
    at a time, recomputing that layer, from the head down to the
    embedding. The weights stay in their stored dtype; each layer's are
    read in float32."""
    import jax
    import jax.numpy as jnp

    stacked = _stack(params)
    sh = shardings(a, mesh)
    layer_sh = {n[len("stack/sub0/"):]: s for n, s in sh.items()
                if n.startswith("stack/sub0/")}

    def layer(x, p32):
        return _layer(p32, x, a, precision, qblock, mesh, fault)

    def fwd(x, p):
        return layer(x, _f32(p)), x

    def bwd(dx, inp):
        x, p = inp
        _, vjp = jax.vjp(layer, x, _f32(p))
        dx, dp = vjp(dx)
        dp = {n: _constrain(g, mesh, *layer_sh[n].spec[1:])
              for n, g in dp.items()}
        return _split_rows(dx, mesh), dp

    B, S = tokens.shape
    keep = _kept(fault, S)
    count = B * jnp.sum(keep)
    embed = params["embed/table"].astype(jnp.float32)
    final = params["final_norm/scale"].astype(jnp.float32)
    head = reference.head_table(params).astype(jnp.float32)
    value = 0.0
    grads = None
    for row in range(B):
        x0 = _split_rows(embed[tokens[row]], mesh)
        xl, xs = jax.lax.scan(fwd, x0, stacked)
        xs = _constrain(xs, mesh, None, AXIS if S % mesh.devices.size == 0
                        else None, None)
        v, head_vjp = jax.vjp(
            lambda x, f, t: _head_loss(x, f, t, labels[row], a, precision,
                                       keep) / count, xl, final, head)
        dxl, dfinal, dhead = head_vjp(jnp.ones((), jnp.float32))
        dx0, dstack = jax.lax.scan(bwd, _split_rows(dxl, mesh),
                                   (xs, stacked), reverse=True)
        g = {"stack/sub0/" + n: d for n, d in dstack.items()}
        g["final_norm/scale"] = dfinal
        g["embed/table"] = jnp.zeros(embed.shape, jnp.float32).at[
            tokens[row]].add(dx0)
        if "lm_head/table" in params:
            g["lm_head/table"] = dhead
        else:
            g["embed/table"] = g["embed/table"] + dhead
        g = {n: jax.lax.with_sharding_constraint(d, sh[n])
             for n, d in g.items()}
        value = value + v
        grads = g if grads is None else {n: grads[n] + g[n] for n in g}
    return value, grads


def leaf_norms(tree: Dict):
    """name -> the float32 2-norm of each array (inside a jitted call)."""
    import jax.numpy as jnp

    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


@functools.lru_cache(maxsize=8)
def make_step(a: reference.Arch, opt: Adam, mesh, *, precision: str,
              qblock: int, fault: Fault = NO_FAULT):
    """One jitted training step: (params, mu, nu, count, tokens, labels)
    -> (params, mu, nu, count, loss, gradient norm per weight, global
    gradient norm). The weights and moments are donated. Kept per
    arguments, so that a process that runs the reference again compiles
    it once."""
    import jax
    import jax.numpy as jnp

    sh = shardings(a, mesh)
    sdt = jnp.dtype(opt.state_dtype)

    def step(params, mu, nu, count, tokens, labels):
        value, grads = value_and_grads(
            params, tokens, labels, a, precision=precision, qblock=qblock,
            mesh=mesh, fault=fault)
        norms = leaf_norms(grads)
        gnorm = jnp.sqrt(sum(jnp.square(v) for v in norms.values()))
        scale = jnp.ones((), jnp.float32)
        if opt.grad_clip is not None:
            scale = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(gnorm, 1e-9))
        count = count + 1
        lr = opt.lr(count)
        bc1 = 1 - opt.b1 ** count.astype(jnp.float32)
        bc2 = 1 - opt.b2 ** count.astype(jnp.float32)
        new_p, new_mu, new_nu = {}, {}, {}
        for n, w in params.items():
            g = grads[n] * scale
            m = mu[n].astype(jnp.float32) * opt.b1 + (1 - opt.b1) * g
            v = nu[n].astype(jnp.float32) * opt.b2 + (1 - opt.b2) * g * g
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + opt.eps)
            w32 = w.astype(jnp.float32)
            delta = delta + opt.weight_decay * w32
            new_p[n] = (w32 - lr * delta).astype(w.dtype)
            new_mu[n], new_nu[n] = m.astype(sdt), v.astype(sdt)
        return new_p, new_mu, new_nu, count, value, norms, gnorm

    return jax.jit(step, in_shardings=(sh, sh, sh, None, None, None),
                   out_shardings=(sh, sh, sh, None, None, None, None),
                   donate_argnums=(0, 1, 2))


def run(a: reference.Arch, seed: int, opt: Adam,
        batches: Sequence[Dict[str, np.ndarray]], devices, *,
        precision: str = "f32", qblock: int = 512,
        fault: Fault = NO_FAULT) -> Dict:
    """Follow ``len(batches)`` steps from the seed's weights. Returns
    ``losses`` (one per step), ``grad`` (each weight's gradient norm at
    the first step), ``grad_norm`` (the first step's global norm),
    ``change`` (each weight's change over all the steps, the 2-norm of the
    difference) and ``seconds``."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    mesh = mesh_of(devices)
    sh = shardings(a, mesh)
    params = reference.init_params(a, seed, sh)
    sdt = jnp.dtype(opt.state_dtype)
    zeros = jax.jit(lambda p: {n: jnp.zeros(w.shape, sdt)
                               for n, w in p.items()}, out_shardings=sh)
    mu, nu = zeros(params), zeros(params)
    count = jnp.zeros((), jnp.int32)
    step = make_step(a, opt, mesh, precision=precision, qblock=qblock,
                     fault=fault)
    losses: List[float] = []
    grad: Dict[str, float] = {}
    gnorm1 = None
    for b in batches:
        params, mu, nu, count, value, norms, gnorm = step(
            params, mu, nu, count, b["tokens"], b["labels"])
        losses.append(float(value))
        if gnorm1 is None:
            grad = {n: float(v) for n, v in norms.items()}
            gnorm1 = float(gnorm)
    del mu, nu
    start = reference.init_params(a, seed, sh)
    diff = jax.jit(lambda p, q: leaf_norms(
        {n: p[n].astype(jnp.float32) - q[n].astype(jnp.float32)
         for n in p}))
    change = {n: float(v) for n, v in diff(params, start).items()}
    del params, start
    return {"losses": losses, "grad": grad, "grad_norm": gnorm1,
            "change": change, "seconds": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

#: a weight whose first gradient is under this share of the median
#: weight's is moved by round-off alone; its change is not compared
STILL = 1e-3


def leaf_gaps(prog: Dict, ref: Dict) -> Dict[str, Dict[str, float]]:
    """Per weight, for ``grad`` and ``change``: the gap between the two
    norms over the reference's norm of that weight or of the median
    weight, whichever is larger (``inf`` where the program's is missing).
    ``change`` leaves out weights whose reference gradient is under
    ``STILL`` of the median weight's."""
    med_g = float(np.median(list(ref["grad"].values())))
    moved = [n for n, g in ref["grad"].items() if g >= STILL * med_g]
    out = {}
    for key, names in (("grad", list(ref["grad"])), ("change", moved)):
        med = float(np.median(list(ref[key].values())))
        out[key] = {n: (abs(prog[key][n] - ref[key][n])
                        / max(ref[key][n], med)
                        if n in prog[key] else float("inf"))
                    for n in names}
    return out


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """What the check compares, from two readings shaped like ``run``'s:

    - ``train_loss_gap``: the largest |loss - reference loss| over the
      steps;
    - ``grad_leaf_gap``: the largest of ``leaf_gaps``' first-step
      gradient gaps;
    - ``change_leaf_gap``: the largest of its gaps of each weight's
      change over the steps.

    A reading that is missing or not finite reads ``inf``."""
    out = {}
    if len(prog["losses"]) != len(ref["losses"]):
        out["train_loss_gap"] = float("inf")
    else:
        out["train_loss_gap"] = max(abs(p - r) for p, r in
                                    zip(prog["losses"], ref["losses"]))
    per = leaf_gaps(prog, ref)
    out["grad_leaf_gap"] = max(per["grad"].values(), default=0.0)
    out["change_leaf_gap"] = max(per["change"].values(), default=0.0)
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}
