"""The paged decode-attention kernel's share of its roofline: the least
time that the FLOPs and bytes of the decode steps in the traced span
allow (``bench/costs/decode_attention_paged``, from the contexts of the
steps the host saw there), over the kernel's summed device time in the
trace (percent).  The kernel runs once per layer per step, so the host's
steps are scaled to the number of calls the trace holds."""
from bench.lib import peaks as peaks_mod, spec

KERNEL = "decode_attention_paged"


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.trace_window
    calls = run.trace.kernel(KERNEL, t0, t1)
    steps = run.decode_steps(t0, t1)
    if not calls or not steps:
        return None
    cost = spec.load_module("costs", KERNEL)
    per_step = sum(peaks_mod.roofline_s(cost.flops(run.cfg, c),
                                        cost.nbytes(run.cfg, c), run.peaks)
                   for c in steps) / len(steps)
    need = per_step * len(calls)
    return 100.0 * need / (sum(e.dur_ns for e in calls) * 1e-9)
