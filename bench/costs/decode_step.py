"""One batched decode step of the whole model.  ``contexts`` holds, for
each live row, the positions its query attends to.

FLOPs: per row, 2 per weight of every layer's projections and SwiGLU
matrices and of the output head, plus 4 * H * dh per attended position in
every layer.  Padding rows need nothing."""
from bench.lib.weights import dims


def layer_params(cfg):
    m = dims(cfg)
    d, H, K, dh, f = m["d"], m["H"], m["K"], m["dh"], m["f"]
    return d * H * dh + 2 * d * K * dh + H * dh * d + 3 * d * f


def flops(cfg, contexts):
    m = dims(cfg)
    per_row = 2.0 * (m["L"] * layer_params(cfg) + m["d"] * m["V"])
    attn = 4.0 * m["H"] * m["dh"] * m["L"] * float(sum(contexts))
    return per_row * len(contexts) + attn
