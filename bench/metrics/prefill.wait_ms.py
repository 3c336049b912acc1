"""What a prefill costs beyond its own device time: over the program's
``decode.prefill`` spans that lie wholly in the traced span, the mean of
each span's length minus the device time of the prefill programs inside
it (milliseconds).  A prefill program runs the flash-attention kernel
once per layer, as ``prefill.mfu`` finds it.  The rest is cache set-up,
dispatch, waiting behind other programs and sampling the first token."""
from bench.lib import spans

KERNEL = "flash_attention"


def read(run):
    if run.trace is None:
        return None
    pre = spans.wholly_in(run, "decode.prefill")
    if not pre:
        return None
    tr, L = run.trace, run.cfg["num_hidden_layers"]
    calls = tr.kernel(KERNEL, *run.trace_window)
    progs = [m for m in tr.module(None, *run.trace_window)
             if len(tr.within(m, calls)) == L]
    waits = [p.dur_ns - sum(spans.overlap_ns(p, m) for m in progs)
             for p in pre]
    return 1e-6 * sum(waits) / len(waits)
