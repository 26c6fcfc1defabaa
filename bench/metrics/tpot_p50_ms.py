"""Time per output token, median over all requests."""
from bench.metrics._tpot import per_request
from bench.stats import percentile


def read(rec):
    per = per_request(rec)
    return 1e3 * percentile(per, 50) if per else None
