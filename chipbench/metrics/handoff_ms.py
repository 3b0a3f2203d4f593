"""Mean host ms of a P->D handoff: each ``step.insert`` span of the
program less its ``wait.drain`` children.  The forced drain waits on
the decode step already in flight, which the next dispatch would wait
on anyway; what is left is the page adoption, the scatter's dispatch
and the block-table update."""
from progspans import insert_self_times


def read(run):
    t = insert_self_times(run)
    return 1e3 * sum(t) / len(t) if t else None
