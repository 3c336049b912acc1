"""Prefill's share of the chip's bf16 peak: over the prefill programs that
lie wholly in the traced span (those that run the flash-attention kernel
once per layer), the FLOPs each prompt requires
(``bench/costs/prefill_step``), over the programs' summed device time
times the peak (percent)."""
from bench.lib import spec, trace as trace_mod

KERNEL = "flash_attention"


def read(run):
    if run.trace is None:
        return None
    tr, L = run.trace, run.cfg["num_hidden_layers"]
    calls = tr.kernel(KERNEL, *run.trace_window)
    cost = spec.load_module("costs", "prefill_step")
    need = took = 0.0
    for m in tr.module(None, *run.trace_window):
        inner = tr.within(m, calls)
        if len(inner) == L:
            need += cost.flops(run.cfg, trace_mod.out_dims(inner[0].name)[2])
            took += m.dur_ns * 1e-9
    return 100.0 * need / (took * run.peaks.bf16_flops) if took else None
