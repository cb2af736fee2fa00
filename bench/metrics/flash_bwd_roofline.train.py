"""flash_bwd_roofline.train: the least time of one chip's share of the
flash backward kernel's work over the traced steps (every visible pair
once: the scores again and four products; ``counts.flash_train_counts``
over the chips) over the self time of the ``flash_attention_bwd``
operations on the device where they took longest."""

from bench import counts
from bench.kernels import _base


def read(trace, facts):
    if facts.get("kind") != "train" or not facts["steps"]:
        return None
    t = max(sum(s for n, s in d.op_seconds().items()
                if _base(n) == "flash_attention_bwd") for d in trace.devices)
    if t <= 0:
        return None
    c = facts["flash_per_chip"]["bwd"]
    least = counts.least_seconds(c["flops"], c["bytes"], facts["peak"])
    return 100.0 * least * facts["steps"] / t
