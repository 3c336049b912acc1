"""Time a batched decode step spends admitting joins (slot merges and
page packs at the step boundary): the summed length of the program's
``decode.admit`` spans inside the ``decode.step`` spans that lie wholly
in the traced span, over the number of those steps (milliseconds a
step)."""
from bench.lib import spans


def read(run):
    if run.trace is None:
        return None
    steps = spans.wholly_in(run, "decode.step")
    if not steps:
        return None
    admits = spans.wholly_in(run, "decode.admit")
    took = sum(e.dur_ns for s in steps for e in spans.inside(s, admits))
    return 1e-6 * took / len(steps)
