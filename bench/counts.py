"""Operations and bytes the work needs, from shapes alone.

The benchmark's own count, kept apart from the program so that no change
to the program can move it. Every count is of the work the algorithm
needs, not of what an implementation happens to do: padding, masked-out
blocks and recomputation (rematerialisation) are not counted, so a
kernel or a step that does more than this reads as a lower share of its
roofline. A multiply-add is two operations.

Shapes come from a configuration file (``reference.model_sizes``).
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

BF16 = 2
F32 = 4


def _m(conf: Dict) -> Dict:
    from bench.reference import model_sizes

    return model_sizes(conf)


def layer_matmul_params(conf: Dict) -> int:
    """Weights of one layer that every token multiplies: the q, k, v and
    output projections and the three SwiGLU matrices."""
    m = _m(conf)
    d, hq, hkv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                         m["head_dim"], m["d_ff"])
    return d * hd * (hq + 2 * hkv) + hq * hd * d + 3 * d * f


def visible_keys(pos: int, window: Optional[int]) -> int:
    """Keys a causal query at ``pos`` attends to (itself included)."""
    n = pos + 1
    return n if window is None else min(n, window)


def visible_pairs(start: int, end: int, window: Optional[int]) -> int:
    """Sum of ``visible_keys`` over query positions ``start .. end-1``."""
    if end <= start:
        return 0
    if window is None or end <= window:
        return (start + 1 + end) * (end - start) // 2
    if start >= window - 1:
        return window * (end - start)
    split = window - 1          # positions < split see pos + 1 keys
    return visible_pairs(start, split, None) + window * (end - split)


def attention_fwd_flops(conf: Dict, pairs: int) -> float:
    """QK^T and PV of every layer over ``pairs`` visible (query, key)
    pairs: 4 * heads * head_dim per pair and layer."""
    m = _m(conf)
    return 4.0 * m["num_heads"] * m["head_dim"] * pairs * m["num_layers"]


def attention_bwd_flops(conf: Dict, pairs: int) -> float:
    """The backward kernel's products per visible pair: the scores again,
    dP = dO V^T, dV = P^T dO, dQ = dS K and dK = dS^T Q."""
    return 2.5 * attention_fwd_flops(conf, pairs)


def forward_flops(conf: Dict, tokens: int, pairs: int,
                  head_rows: Optional[int] = None) -> float:
    """One forward pass over ``tokens`` positions whose attention covers
    ``pairs`` visible pairs; the output head runs on ``head_rows``
    positions (all of them by default)."""
    m = _m(conf)
    rows = tokens if head_rows is None else head_rows
    return (2.0 * layer_matmul_params(conf) * m["num_layers"] * tokens
            + 2.0 * m["d_model"] * m["vocab_size"] * rows
            + attention_fwd_flops(conf, pairs))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_step_flops(conf: Dict, seq_len: int, batch: int) -> float:
    """Forward and backward of one step, each sequence causal from 0:
    the backward pass of a matmul is twice its forward; attention's is
    ``attention_bwd_flops``. Recomputation is not counted."""
    m = _m(conf)
    pairs = visible_pairs(0, seq_len, m.get("window")) * batch
    tokens = seq_len * batch
    matmul = (2.0 * layer_matmul_params(conf) * m["num_layers"]
              + 2.0 * m["d_model"] * m["vocab_size"]) * tokens
    return 3.0 * matmul + attention_fwd_flops(conf, pairs) \
        + attention_bwd_flops(conf, pairs)


def flash_train_counts(conf: Dict, seq_len: int, batch: int) -> Dict:
    """The flash kernels' work over one training step: every visible pair
    once forward and once backward, over all layers. Bytes are each
    operand read once and each result written once, per layer."""
    m = _m(conf)
    pairs = visible_pairs(0, seq_len, m.get("window")) * batch
    L, hq, hkv, hd = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"])
    tok = seq_len * batch
    q = tok * hq * hd * BF16
    kv = 2 * tok * hkv * hd * BF16
    lse = tok * hq * F32
    fwd_bytes = L * (q + kv + q + lse)              # q, k, v -> o, lse
    bwd_bytes = L * (2 * q + kv + 2 * lse + q + kv)  # q, do, k, v, lse,
    #                                                 delta -> dq, dk, dv
    return {"fwd": {"flops": attention_fwd_flops(conf, pairs),
                    "bytes": float(fwd_bytes)},
            "bwd": {"flops": attention_bwd_flops(conf, pairs),
                    "bytes": float(bwd_bytes)}}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def decode_step_flops(conf: Dict, cache_lens: Iterable[int]) -> float:
    """One decode step: each active row emits one token at position
    ``cache_len`` and attends to the keys visible from there."""
    m = _m(conf)
    lens = list(cache_lens)
    pairs = sum(visible_keys(c, m.get("window")) for c in lens)
    return forward_flops(conf, len(lens), pairs)


def paged_decode_counts(conf: Dict, cache_lens: Iterable[int]) -> Dict:
    """The paged-decode kernel over one step, all layers: the visible keys
    and values of every active row read once from HBM (bf16), each query
    read and each output written once."""
    m = _m(conf)
    lens = list(cache_lens)
    L, hq, hkv, hd = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"])
    keys = sum(visible_keys(c, m.get("window")) for c in lens)
    kv_bytes = 2 * keys * hkv * hd * BF16
    qo_bytes = 2 * len(lens) * hq * hd * BF16
    return {"flops": attention_fwd_flops(conf, keys),
            "bytes": float(L * (kv_bytes + qo_bytes))}


def prefill_flops(conf: Dict, prompt_len: int) -> float:
    """A monolithic prefill of ``prompt_len`` real tokens (no padding); the
    output head runs on the last position only."""
    m = _m(conf)
    pairs = visible_pairs(0, prompt_len, m.get("window"))
    return forward_flops(conf, prompt_len, pairs, head_rows=1)


def prefill_flash_counts(conf: Dict, prompt_len: int) -> Dict:
    """The flash forward kernel over one prefill, all layers."""
    m = _m(conf)
    pairs = visible_pairs(0, prompt_len, m.get("window"))
    L, hq, hkv, hd = (m["num_layers"], m["num_heads"], m["num_kv_heads"],
                      m["head_dim"])
    q = prompt_len * hq * hd * BF16
    kv = 2 * prompt_len * hkv * hd * BF16
    return {"flops": attention_fwd_flops(conf, pairs),
            "bytes": float(L * (q + kv + q))}


def least_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
