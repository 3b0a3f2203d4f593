"""Share (%) of the counted requests' prompt tokens that the prefill
took from the prefix cache rather than computed: their ``step.prefill``
spans' ``reused`` over ``reused`` plus ``computed``, over the whole run."""
from progspans import prefill_tokens


def read(run):
    t = prefill_tokens(run)
    if t is None or sum(t) == 0:
        return None
    return 100.0 * t[0] / sum(t)
