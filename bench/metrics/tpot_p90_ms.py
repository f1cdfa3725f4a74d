"""90th percentile of the time per output token after the first,
(last - first token) / (tokens - 1) on the host clock, over every
request that arrived in the window and decodes at least ``MIN_TOKENS``
tokens, so that each reading spans several rounds.  A request that did
not finish counts as missing every limit."""
from bench.harness import percentile

MIN_TOKENS = 8


def read(run):
    return percentile([1e3 * (r.token_t[-1] - r.token_t[0])
                       / (len(r.token_t) - 1) if r.complete
                       else float("inf")
                       for r in run.population
                       if r.req.n_out >= MIN_TOKENS], 90)
