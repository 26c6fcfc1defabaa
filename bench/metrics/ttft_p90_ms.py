"""Time to first token, 90th percentile over every request sent in the
window, from the time each was due (host clock). A request that never
got a first token waited until the drain ended."""
from bench.stats import percentile


def read(rec):
    return 1e3 * percentile(
        [(rec.drain_end if r.t_first is None else r.t_first) - r.due
         for r in rec.requests], 90)
