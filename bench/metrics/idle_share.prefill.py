"""idle_share.prefill: the device's idle share of the time the engine had
work, 1 - busy / (window - waiting), where waiting is the open loop's
``bench/idle`` (nothing in the engine, the next arrival not yet due);
the largest over the devices. So a faster prefill, which leaves the loop
waiting longer, does not read as more idle."""

import math


def read(trace, facts):
    shares = trace.engaged_idle_shares()
    if not shares or any(math.isnan(x) for x in shares):
        return None
    return 100.0 * max(shares)
