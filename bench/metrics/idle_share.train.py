"""idle_share.train: 1 - busy / window, where busy is the union of a
device's operations inside the traced window of training steps; the
largest over the devices."""


def read(trace, facts):
    if facts.get("kind") != "train":
        return None
    return 100.0 * max(trace.idle_shares())
