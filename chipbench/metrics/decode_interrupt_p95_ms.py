"""95th percentile, by nearest rank, in ms, of the time the decode
batch is held by other work: over each pair of consecutive
``step.decode`` spans of one instance that share a request, the
interval between their starts less the median interval that no
``step.prefill`` or ``step.insert`` started inside (0 for those).  It
holds the prefill's device time, which the host cannot tell from the
decode step it queues behind.  Intervals over a profiler pause are
left out."""
from progspans import decode_extras
from stats import nearest_rank


def read(run):
    x = decode_extras(run)
    return 1e3 * nearest_rank(x, 0.95) if x else None
