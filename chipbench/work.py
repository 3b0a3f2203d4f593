"""Operations and bytes that the served work needs, from the shapes.

These count what the algorithm requires, not what an implementation
happens to do: a decode row attends over its real context (not the
pages a step gathers), a prefill computes its real new tokens (not the
padded bucket, and not a prefix it reuses from the cache) and the
logits of its last position only.  A later change that gathers fewer
pages or pads less therefore shows as a higher share of the peak, and
these counts do not go stale.  Each architecture module counts its own
(``archs/<architecture>.py``); a multiply-add counts as two operations.
"""
from __future__ import annotations

from model import arch


def prefill_flops(c: dict, computed: int, ctx: int) -> int:
    """A prefill of ``computed`` new tokens after ``ctx`` reused ones."""
    return arch(c).prefill_flops(c, computed, ctx)


def decode_flops(c: dict, contexts) -> int:
    """One decode step over rows with these resident contexts."""
    return arch(c).decode_flops(c, contexts)


def decode_bytes(c: dict, contexts) -> int:
    """Bytes one decode step over rows with these contexts must move."""
    return arch(c).decode_bytes(c, contexts)
