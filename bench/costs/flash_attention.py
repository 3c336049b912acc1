"""One call of the flash-attention kernel: causal self-attention over one
prompt of ``n`` tokens in one layer, batch 1, as prefill runs it.

FLOPs: QK^T and PV over the causal triangle, 2 * 2 * H * dh * n(n+1)/2.
Bytes: q, k and v read once and the output written once, in bfloat16,
the least the kernel has to move."""
from bench.lib.weights import dims


def flops(cfg, n):
    m = dims(cfg)
    return 4.0 * m["H"] * m["dh"] * n * (n + 1) / 2


def nbytes(cfg, n):
    m = dims(cfg)
    return 2.0 * n * m["dh"] * (2 * m["H"] + 2 * m["K"])
