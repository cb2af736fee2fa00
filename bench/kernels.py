"""Which device operations of a trace are which kernel.

The program's Pallas kernels carry no ``name=`` of their own; a call's
operation in the trace is named after the function that wraps it
(``paged_decode_attention.9``), so the readers match those names.
PERF.md lists the names seen on the chip.
"""

from __future__ import annotations


def _base(name: str) -> str:
    """``paged_decode_attention.9`` -> ``paged_decode_attention``."""
    head, _, tail = name.rpartition(".")
    return head if tail.isdigit() else name


def is_paged_decode(name: str) -> bool:
    return _base(name) == "paged_decode_attention"
