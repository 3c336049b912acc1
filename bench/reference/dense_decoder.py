"""Plain float32 reference of a dense decoder, apart from the program.

RMSNorm with a ``1 + scale`` gain, rotary embedding over split halves,
causal grouped-query softmax attention (query head j reads key/value
head j // (H / K)), SwiGLU MLP, a head of its own or, where the
configuration ties it, the token embedding's transpose.  Written out in
``jax.numpy`` at ``HIGHEST`` matmul precision, one jitted layer applied
layer by layer, so that it fits beside nothing else on the chip.

``mode="fp8"`` is the control: every matrix product rounds both of its
operands to float8 (e4m3, one scale per tensor) first, the precision one
step below the bfloat16 that the program computes in.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MODES = ("f32", "fp8")


def _fp8(t: jax.Array) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(mode: str, eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _norm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + scale.astype(F32))


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _layer(x, p, cos, sin, *, cfg_items: Tuple, mode: str):
    cfg = dict(cfg_items)
    H, K, dh = cfg["H"], cfg["K"], cfg["dh"]
    S = x.shape[0]

    def rope(t):                                       # (S, heads, dh)
        a, b = t[..., :dh // 2], t[..., dh // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    h = _norm(x, p["norm1"]["scale"], cfg["eps"])
    q = rope(_mm(mode, "sd,dhk->shk", h, p["attn"]["wq"]))
    k = rope(_mm(mode, "sd,dhk->shk", h, p["attn"]["wk"]))
    v = _mm(mode, "sd,dhk->shk", h, p["attn"]["wv"])
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = _mm(mode, "qhk,thk->hqt", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm(mode, "hqt,thk->qhk", a, v)
    x = x + _mm(mode, "shk,hkd->sd", o, p["attn"]["wo"])
    h = _norm(x, p["norm2"]["scale"], cfg["eps"])
    g = jax.nn.silu(_mm(mode, "sd,df->sf", h, p["mlp"]["wg"]))
    u = _mm(mode, "sd,df->sf", h, p["mlp"]["wu"])
    return x + _mm(mode, "sf,fd->sd", g * u, p["mlp"]["wd"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode", "tied"))
def _head(x, scale, w, *, cfg_items: Tuple, mode: str, tied: bool):
    """``w``: the head (d, V), or with ``tied`` the embedding (V, d)."""
    cfg = dict(cfg_items)
    x = _norm(x, scale, cfg["eps"])
    return _mm(mode, "sd,vd->sv" if tied else "sd,dv->sv", x, w)


def _cfg_items(cfg: Dict[str, Any]) -> Tuple:
    return (("H", cfg["num_attention_heads"]),
            ("K", cfg["num_key_value_heads"]), ("dh", cfg["head_dim"]),
            ("eps", float(cfg["rms_norm_eps"])),
            ("theta", float(cfg["rope_theta"])))


def logits(cfg: Dict[str, Any], weights: Dict[str, Any], tokens,
           mode: str = "f32") -> jax.Array:
    """Next-token logits at every position of ``tokens`` (S,): (S, V)
    float32, on the default device.  ``weights``: unit -> tree, as
    :func:`bench.lib.weights.make` gives them."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r}")
    items = _cfg_items(cfg)
    c = dict(items)
    dh = c["dh"]
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    inv = 1.0 / c["theta"] ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = weights["embed"]["tok"].astype(F32)[tokens]
    for j in range(cfg["num_hidden_layers"]):
        x = _layer(x, weights[f"block_{j:03d}"], cos, sin,
                   cfg_items=items, mode=mode)
    tied = bool(cfg["tie_word_embeddings"])
    w = weights["embed"]["tok"] if tied else weights["final"]["head"]["w"]
    return _head(x, weights["final"]["norm"]["scale"], w, cfg_items=items,
                 mode=mode, tied=tied)
