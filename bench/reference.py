"""Plain reference of the benchmark's decoder LMs.

A dense Llama-style decoder written out in ``jax.numpy`` from the published
description (pre-norm RMSNorm, rotary embeddings with the half-split
rotation, grouped-query attention, optional causal sliding window, SwiGLU
MLP, untied output head), computed in float32 at the highest matmul
precision. It imports nothing of the program under test. ``init_params``
makes the benchmark's weights from the seed; the program is handed them,
and the reference makes them again for itself, so it never reads weights
that the program holds.

Everything runs in blocks so that it fits next to nothing else on the
device: layers one at a time under ``lax.scan``, attention in blocks of
query rows, logits only at the positions asked for.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 (one scale per tensor) before the float32 product, the
precision step below the bfloat16 the configurations state.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: rows of the embedding and the output head are padded to this multiple
VOCAB_MULTIPLE = 32


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    window: Optional[int]
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    param_dtype: str

    @property
    def padded_vocab(self) -> int:
        m = VOCAB_MULTIPLE
        return (self.vocab_size + m - 1) // m * m

    @classmethod
    def from_config(cls, conf: Dict) -> "Arch":
        return cls(**model_sizes(conf))


def model_sizes(conf: Dict) -> Dict:
    """The sizes of a configuration file, whose keys are those of the
    published ``config.json`` (Hugging Face names)."""
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    return dict(num_layers=conf["num_hidden_layers"], d_model=d,
                num_heads=hq, num_kv_heads=conf["num_key_value_heads"],
                head_dim=conf.get("head_dim") or d // hq,
                d_ff=conf["intermediate_size"],
                vocab_size=conf["vocab_size"],
                window=conf.get("sliding_window"),
                rope_theta=float(conf["rope_theta"]),
                norm_eps=float(conf["rms_norm_eps"]),
                tie_embeddings=bool(conf["tie_word_embeddings"]),
                param_dtype=conf["torch_dtype"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_layout(a: Arch) -> Dict:
    """name -> (shape, init, stddev) in the order the seed's keys are drawn.

    The benchmark's weight rule: the keys of ``PRNGKey(seed)`` are split
    once per tensor, in the sorted order of the tensors' names; a normal
    tensor is a standard normal draw times its stddev, rounded to the
    parameter dtype. The stddev is the tensor's fan-in to the power -1/2
    (``d_model`` for the embedding and the head), so that activations
    keep the unit scale of a trained model. Per-layer tensors are stacked
    over the layers. The program is handed these weights.
    """
    L, d, hq, hkv, hd, f = (a.num_layers, a.d_model, a.num_heads,
                            a.num_kv_heads, a.head_dim, a.d_ff)
    emb = ((a.padded_vocab, d), "normal", d ** -0.5)
    out = {
        "embed/table": emb,
        "final_norm/scale": ((d,), "ones", None),
    }
    if not a.tie_embeddings:
        out["lm_head/table"] = emb
    out.update({
        "stack/sub0/mixer/norm/scale": ((L, d), "ones", None),
        "stack/sub0/mixer/wk": ((L, d, hkv, hd), "normal", d ** -0.5),
        "stack/sub0/mixer/wo": ((L, hq, hd, d), "normal", (hq * hd) ** -0.5),
        "stack/sub0/mixer/wq": ((L, d, hq, hd), "normal", d ** -0.5),
        "stack/sub0/mixer/wv": ((L, d, hkv, hd), "normal", d ** -0.5),
        "stack/sub0/mlp/norm/scale": ((L, d), "ones", None),
        "stack/sub0/mlp/w1": ((L, d, f), "normal", d ** -0.5),
        "stack/sub0/mlp/w2": ((L, f, d), "normal", f ** -0.5),
        "stack/sub0/mlp/w3": ((L, d, f), "normal", d ** -0.5),
    })
    return out


def init_params(a: Arch, seed: int, shardings=None):
    """The weights of ``seed``, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    layout = param_layout(a)
    dtype = jnp.dtype(a.param_dtype)

    def make(key):
        keys = jax.random.split(key, len(layout))
        out = {}
        for i, (name, (shape, init, std)) in enumerate(layout.items()):
            if init == "ones":
                out[name] = jnp.ones(shape, dtype)
                continue
            z = jax.random.normal(keys[i], shape, jnp.float32)
            # keep the draw and the scaling two separate roundings
            z = jax.lax.optimization_barrier(z)
            out[name] = (z * std).astype(dtype)
        return out

    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def nest(flat: Dict) -> Dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``: the program's tree layout."""
    out: Dict = {}
    for name, x in flat.items():
        node = out
        *path, last = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = x
    return out


def flatten(tree, prefix: str = "") -> Dict:
    """The inverse of ``nest``."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "/"))
        else:
            out[name] = v
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _quantize_fp8(x):
    """Round to float8 e4m3 with one scale per tensor (the control)."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, x, w, precision: str):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _quantize_fp8(x), _quantize_fp8(w)
    return jnp.einsum(spec, x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, H, D); rotate the two halves of each head by position."""
    import jax.numpy as jnp

    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, a: Arch, qblock: int, precision: str):
    """Causal (windowed) attention over query blocks. q: (S, Hq, D),
    k, v: (S, Hkv, D) -> (S, Hq, D). Each block sees only the keys its
    rows can reach."""
    import jax
    import jax.numpy as jnp

    S, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qblock = min(qblock, S)
    if S % qblock:
        raise ValueError(f"sequence {S} is not a multiple of {qblock}")
    nb = S // qblock
    span = S if a.window is None else min(S, a.window + qblock)
    qg = q.reshape(S, hkv, g, hd)

    def block(i):
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
        s = jnp.where(vis, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("ngqk,knd->qngd", p, vb, precision)
        return o.reshape(qblock, hq, hd)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(S, hq, hd)


def hidden(params, tokens, a: Arch, *, precision: str = "f32",
           qblock: int = 256):
    """Final-normed hidden states (S, d) of one sequence of token ids."""
    import jax
    import jax.numpy as jnp

    x = params["embed/table"][tokens].astype(jnp.float32)
    layer_names = [n for n in params if n.startswith("stack/")]
    stacked = {n: params[n] for n in layer_names}

    def layer(x, p):
        pre = "stack/sub0/"
        h = _rmsnorm(x, p[pre + "mixer/norm/scale"], a.norm_eps)
        q = _mm("sd,dhk->shk", h, p[pre + "mixer/wq"], precision)
        k = _mm("sd,dhk->shk", h, p[pre + "mixer/wk"], precision)
        v = _mm("sd,dhk->shk", h, p[pre + "mixer/wv"], precision)
        q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
        o = _attention(q, k, v, a, qblock, precision)
        x = x + _mm("shk,hkd->sd", o, p[pre + "mixer/wo"], precision)
        h = _rmsnorm(x, p[pre + "mlp/norm/scale"], a.norm_eps)
        u = _mm("sd,df->sf", h, p[pre + "mlp/w1"], precision)
        gt = _mm("sd,df->sf", h, p[pre + "mlp/w3"], precision)
        act = jax.nn.silu(u) * gt
        x = x + _mm("sf,fd->sd", act, p[pre + "mlp/w2"], precision)
        return x, None

    x, _ = jax.lax.scan(layer, x, stacked)
    return _rmsnorm(x, params["final_norm/scale"], a.norm_eps)


def head_table(params):
    return params.get("lm_head/table", params["embed/table"])


def logits(params, h, a: Arch, precision: str = "f32"):
    """(n, d) hidden -> (n, vocab) logits, padded rows dropped."""
    lg = _mm("nd,vd->nv", h, head_table(params), precision)
    return lg[:, :a.vocab_size]


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------

def _bucket(n: int, quantum: int) -> int:
    return max(quantum, -(-n // quantum) * quantum)


def served_gaps(a: Arch, seed: int, seqs: Sequence[Tuple[List[int],
                                                         List[int]]],
                *, control: bool = False, seq_quantum: int = 2048,
                pos_quantum: int = 512) -> List[float]:
    """For each (prompt, served tokens): the widest gap, over the served
    positions, between the reference's largest logit and its logit of the
    token that was served. With ``control`` the token is the one that the
    fp8 reference puts first at that position instead.

    Sequences are padded to a multiple of ``seq_quantum`` tokens (the
    padding lies after every row that is read, so causality hides it)
    and the positions to a multiple of ``pos_quantum``, so that a few
    compiled programs serve every request."""
    import jax
    import jax.numpy as jnp

    params = init_params(a, seed)

    def make(precision):
        @jax.jit
        def fn(params, tokens, pos):
            h = hidden(params, tokens, a, precision=precision)
            return logits(params, h[pos], a, precision)
        return fn

    ref_fn = make("f32")
    ctl_fn = make("fp8") if control else None
    out = []
    for prompt, served in seqs:
        seq = list(prompt) + list(served[:-1])
        n = len(served)
        S = _bucket(len(seq), seq_quantum)
        tokens = np.zeros((S,), np.int32)
        tokens[:len(seq)] = seq
        npos = _bucket(n, pos_quantum)
        pos = np.full((npos,), len(prompt) - 1, np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        ref = np.asarray(ref_fn(params, tokens, pos))[:n]
        if control:
            pick = np.asarray(ctl_fn(params, tokens, pos))[:n].argmax(-1)
        else:
            pick = np.asarray(served, np.int64)
        best = ref.max(-1)
        got = ref[np.arange(n), pick]
        out.append(float(np.max(best - got)))
    del params
    return out
