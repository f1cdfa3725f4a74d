"""90th percentile, over every request that arrived in the window, of
the host-clock time from its scheduled arrival to its first decoded
token.  A request that never got one counts as missing every limit."""
from bench.harness import percentile


def read(run):
    return percentile([1e3 * (r.token_t[0] - r.arrival) if r.token_t
                       else float("inf") for r in run.population], 90)
