"""Share of the engine's step time spent outside the decode quantum:
admission (prefill and the admit scatter) and the engine's host work.
Host span around each step() in the window minus its StepReport.dt."""


def read(rec):
    steps = rec.window_steps()
    span = sum(s.t1 - s.t0 for s in steps)
    if span <= 0:
        return None
    return 100.0 * (span - sum(s.dt for s in steps)) / span
