"""paged_decode_roofline.decode: the least time of the paged-decode
kernel's work in the traced window (every visible key and value read once,
``counts.paged_decode_counts``; HBM-bound) over the device time of its
calls."""

from bench import counts
from bench.kernels import is_paged_decode


def read(trace, facts):
    t = trace.op_seconds(is_paged_decode)
    if t <= 0:
        return None
    least = 0.0
    for rows in facts["decode_rows"]:
        c = counts.paged_decode_counts(facts["conf"], rows)
        least += counts.least_seconds(c["flops"], c["bytes"], facts["peak"])
    return 100.0 * least / t
