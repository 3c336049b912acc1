"""The flash-attention kernel's share of its roofline: over its calls in
the traced span, the least time their FLOPs and bytes allow
(``bench/costs/flash_attention``; a call's prompt length is its result's
sequence dimension), over the calls' summed device time (percent)."""
from bench.lib import peaks as peaks_mod, spec, trace as trace_mod

KERNEL = "flash_attention"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.kernel(KERNEL, *run.trace_window)
    if not calls:
        return None
    cost = spec.load_module("costs", KERNEL)
    need = 0.0
    for e in calls:
        n = trace_mod.out_dims(e.name)[2]
        need += peaks_mod.roofline_s(cost.flops(run.cfg, n),
                                     cost.nbytes(run.cfg, n), run.peaks)
    return 100.0 * need / (sum(e.dur_ns for e in calls) * 1e-9)
