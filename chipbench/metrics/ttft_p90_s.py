"""90th percentile, in s, of the time to first token of all requests
counted in the run, from each request's due time to its first token on
the host clock; a request that never got one counts as infinite."""
from stats import nearest_rank, ttfts


def read(run):
    t = ttfts(run)
    return nearest_rank(t, 0.90) if t else None
