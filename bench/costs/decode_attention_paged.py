"""One call of the paged decode-attention kernel: one layer of one batched
decode step.  ``contexts`` holds, for each live row, the number of cached
positions its query attends to (its position + 1); rows without a
request are padding and need nothing.

FLOPs: QK^T and PV, 4 * H * dh per attended position.
Bytes: each live row's keys and values over its context, its query read
and its output written, in bfloat16."""
from bench.lib.weights import dims


def flops(cfg, contexts):
    m = dims(cfg)
    return 4.0 * m["H"] * m["dh"] * float(sum(contexts))


def nbytes(cfg, contexts):
    m = dims(cfg)
    kv = 2.0 * 2 * m["K"] * m["dh"] * float(sum(contexts))
    return kv + 2.0 * 2 * m["H"] * m["dh"] * len(contexts)
