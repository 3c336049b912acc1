"""One prefill of an ``n``-token prompt, batch 1.

FLOPs: 2 per weight of every layer's projections and SwiGLU matrices per
token, causal attention (4 * H * dh * n(n+1)/2 per layer), and the output
head for the last position, the only one whose logits the request
needs."""
from bench.lib.weights import dims


def flops(cfg, n):
    m = dims(cfg)
    d, H, K, dh, f = m["d"], m["H"], m["K"], m["dh"], m["f"]
    layer = d * H * dh + 2 * d * K * dh + H * dh * d + 3 * d * f
    return (2.0 * n * m["L"] * layer
            + 4.0 * H * dh * m["L"] * n * (n + 1) / 2
            + 2.0 * d * m["V"])
