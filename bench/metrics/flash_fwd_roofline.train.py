"""flash_fwd_roofline.train: the least time of one chip's share of the
flash forward kernel's work over the traced steps (every visible pair
once, ``counts.flash_train_counts`` over the chips) over the self time of
the ``flash_attention_fwd`` operations on the device where they took
longest."""

from bench import counts
from bench.kernels import _base


def read(trace, facts):
    if facts.get("kind") != "train" or not facts["steps"]:
        return None
    t = max(sum(s for n, s in d.op_seconds().items()
                if _base(n) == "flash_attention_fwd") for d in trace.devices)
    if t <= 0:
        return None
    c = facts["flash_per_chip"]["fwd"]
    least = counts.least_seconds(c["flops"], c["bytes"], facts["peak"])
    return 100.0 * least * facts["steps"] / t
