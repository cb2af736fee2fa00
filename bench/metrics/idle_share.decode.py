"""Device idle share: 1 - busy / window, where busy is the union of the
device's operations inside the traced window; the largest over the
devices."""


def read(trace, facts):
    return 100.0 * max(trace.idle_shares())
