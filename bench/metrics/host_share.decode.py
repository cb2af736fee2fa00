"""host_share.decode: the share of the traced window in which the engine's
host loop was not blocked on the device, 1 - (``engine/decode_wait`` +
``engine/prefill_wait`` span time) / window. Beside ``idle_share.decode``
it says how much of the device's idle time the host's own work explains.
None where the program has no such spans."""

from bench import scopes

WAITS = ("engine/decode_wait", "engine/prefill_wait")


def read(trace, facts):
    tl = scopes.for_reduced(trace)
    if tl is None or not any(n in WAITS for _, _, n in tl.host):
        return None
    return 100.0 * (1.0 - tl.span_seconds(WAITS) / tl.window_s)
