"""Time a request waits for KV pages: the mean length of the program's
``decode.kv_alloc`` spans (both reservation attempts, blocking under the
page budget) that lie wholly in the traced span (milliseconds)."""
from bench.lib import spans


def read(run):
    return spans.mean_ms(run, "decode.kv_alloc")
