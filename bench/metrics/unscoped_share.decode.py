"""unscoped_share.decode: device self time in operations under none of the
program's compute scopes (``decode/embed``, ``attn/*``, ``mlp``,
``decode/head`` and their ``prefill/`` twins; ``scopes.compute_scope``)
over busy time, in the traced window: the compiler's copies and
rematerialisations and the layer scan's own slicing. None where no
operation carries a compute scope (a program without them)."""

from bench import scopes


def read(trace, facts):
    tl = scopes.for_reduced(trace)
    if tl is None or not tl.has_scopes():
        return None
    return 100.0 * tl.scope_seconds().get("unscoped", 0.0) / tl.busy_s()
