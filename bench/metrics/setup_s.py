"""Process start to the window's opening: imports, device start, weights,
engine, warm-up and every compile (host clock)."""


def read(rec):
    return rec.setup_s
