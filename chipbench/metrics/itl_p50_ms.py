"""Median, in ms, of all gaps between consecutive tokens of all requests
counted in the run, on the host clock."""
from stats import nearest_rank, token_gaps


def read(run):
    g = token_gaps(run)
    return 1e3 * nearest_rank(g, 0.50) if g else None
