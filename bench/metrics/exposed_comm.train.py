"""exposed_comm.train: the share of the traced window in which a
collective operation (all-gather, all-reduce, reduce-scatter,
collective-permute, all-to-all, with their start and done events) runs on
a device and no other operation does (``bench/overlap.py``, by the HLO
opcode); the largest over the devices."""

from bench import overlap


def read(trace, facts):
    if facts.get("kind") != "train":
        return None
    return 100.0 * max(overlap.exposed_shares(facts["trace_dir"],
                                              facts["chips"]))
