"""Mean decode step: the decode quanta's seconds (StepReport.dt, host
clock, ended by the quantum's one blocking fetch) over the decode steps
they ran."""


def read(rec):
    steps = rec.window_steps()
    n = sum(s.quanta for s in steps) * rec.decode_quantum
    if not n:
        return None
    return 1e3 * sum(s.dt for s in steps) / n
