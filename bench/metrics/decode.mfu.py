"""The decode step's share of the chip's bf16 peak: the FLOPs that the
decode steps require (``bench/costs/decode_step``, from the contexts of
the steps the host saw in the traced span), over the device time of the
decode-step programs (``jit_step``) in the trace times the peak
(percent).  Host steps are scaled to the number of programs traced."""
from bench.lib import spec

MODULE = "jit_step"


def read(run):
    if run.trace is None:
        return None
    t0, t1 = run.trace_window
    mods = run.trace.module(MODULE, t0, t1)
    steps = run.decode_steps(t0, t1)
    if not mods or not steps:
        return None
    cost = spec.load_module("costs", "decode_step")
    need = sum(cost.flops(run.cfg, c) for c in steps) / len(steps) * len(mods)
    took = sum(e.dur_ns for e in mods) * 1e-9
    return 100.0 * need / (took * run.peaks.bf16_flops)
