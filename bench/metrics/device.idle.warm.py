"""Share of the traced span of a warm window in which no operation ran
on the device: 1 minus the union of the device's op intervals over the
span (percent)."""


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.trace_window
    return 100.0 * (1.0 - run.trace.busy_s(t0, t1) / (t1 - t0))
