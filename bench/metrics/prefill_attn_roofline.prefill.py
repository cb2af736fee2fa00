"""prefill_attn_roofline.prefill: the least time of the prefill attention
work in the traced window (every visible pair of each prompt at its real
length, and every query, key and value read once and each output written
once, ``counts.prefill_flash_counts``) over the self time of the flash
forward kernel that prefill calls (``kernels.is_flash_fwd``)."""

from bench import counts
from bench.kernels import is_flash_fwd


def read(trace, facts):
    t = trace.op_seconds(is_flash_fwd)
    if t <= 0 or not facts["prefill_lens"]:
        return None
    least = 0.0
    for n in facts["prefill_lens"]:
        c = counts.prefill_flash_counts(facts["conf"], n)
        least += counts.least_seconds(c["flops"], c["bytes"], facts["peak"])
    return 100.0 * least / t
