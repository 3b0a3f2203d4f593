"""90th percentile, by nearest rank, in ms, of the prefill queue waits
of the requests counted in the run: the program's ``queue.prefill``
spans, from the cluster handing a request to the prefill router to the
prefill step that takes it, on the host clock.  Waits over a profiler
pause are left out."""
from progspans import prefill_waits
from stats import nearest_rank


def read(run):
    w = prefill_waits(run)
    return 1e3 * nearest_rank(w, 0.90) if w else None
