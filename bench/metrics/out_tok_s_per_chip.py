"""Output tokens served by steps that ended inside the window, per second
of the window and per chip (first tokens included)."""


def read(rec):
    n = sum(s.emitted for s in rec.window_steps())
    return n / rec.window_s / rec.chips
