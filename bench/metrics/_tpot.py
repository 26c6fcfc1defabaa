"""Time per output token of each request: (last token - first token) /
(tokens - 1), host clock, over every request sent in the window."""


def per_request(rec):
    return [(r.t_last - r.t_first) / (r.n_out - 1) for r in rec.requests
            if r.n_out > 1]
