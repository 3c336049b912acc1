"""Building blocks: norms, RoPE, GQA attention (full / sliding-window),
SwiGLU / GELU MLPs, embeddings.

All parameters are plain dicts; every function is pure.  Activation tensors
are annotated with *logical axis names* via :func:`repro.distributed.sharding
.constrain` so the same model code runs single-device (no-op) and under any
mesh/rule set (pjit constraints).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import quant
from repro.distributed.sharding import constrain

PyTree = Any


# ---------------------------------------------------------------------------
# initializers (the "PISeL-faithful" expensive construction path)
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype,
               fan_in: Optional[int] = None) -> jax.Array:
    """He/Kaiming-style normal init — deliberately the *real* numerical
    initialization the paper's MiniLoader elides (Sec. II-B)."""
    fan = fan_in if fan_in is not None else shape[0]
    std = math.sqrt(2.0 / max(fan, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key: jax.Array, shape: Tuple[int, ...], dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_params(cfg, key) -> PyTree:
    d = cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((d,), cfg.param_dtype)}
    return {"scale": jnp.ones((d,), cfg.param_dtype),
            "bias": jnp.zeros((d,), cfg.param_dtype)}


def apply_norm(cfg, p: PyTree, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(dh: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                             # (..., S, 1, dh/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention block (GQA, optional sliding window)
# ---------------------------------------------------------------------------

def attn_params(cfg, key: jax.Array) -> PyTree:
    d, h, k, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h, dh), cfg.param_dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, k, dh), cfg.param_dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, k, dh), cfg.param_dtype, fan_in=d),
        "wo": dense_init(ks[3], (h, dh, d), cfg.param_dtype, fan_in=h * dh),
    }


def qkv_project(cfg, p: PyTree, x: jax.Array, positions: jax.Array,
                *, rope: bool = True):
    """x: (B, S, D) -> q (B,S,H,dh), k/v (B,S,K,dh)."""
    cd = cfg.compute_dtype
    q = quant.einsum("bsd,dhk->bshk", x, p["wq"], cd)
    k = quant.einsum("bsd,dhk->bshk", x, p["wk"], cd)
    v = quant.einsum("bsd,dhk->bshk", x, p["wv"], cd)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(cfg, p: PyTree, o: jax.Array) -> jax.Array:
    """o: (B, S, H, dh) -> (B, S, D)."""
    y = quant.einsum("bshk,hkd->bsd", o, p["wo"], cfg.compute_dtype,
                     n_contract=2)
    return constrain(y, "batch", "seq", "embed")


def attention_block(cfg, p: PyTree, x: jax.Array, positions: jax.Array,
                    *, window: int = -1, return_kv: bool = False):
    """Self-attention sub-block (no residual, no norm).

    window: -1 -> use cfg.sliding_window; 0 -> full; >0 -> that window.
    return_kv: also return the rotated (k, v) for prefill cache writes.
    """
    from repro.kernels import ops  # local import: avoid import cycle
    if window < 0:
        window = cfg.sliding_window
    q, k, v = qkv_project(cfg, p, x, positions)
    o = ops.flash_attention(q, k, v, causal=cfg.causal, window=window)
    o = constrain(o, "batch", "seq", "heads", None)
    y = attn_out(cfg, p, o)
    if return_kv:
        return y, k, v
    return y


def attention_decode(cfg, p: PyTree, x: jax.Array, pos: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     *, window: int = -1):
    """Single-token decode.  x: (B, 1, D); caches: (B, K, S_max, dh)
    kv-head-major (dot-friendly, no transposes — §Perf iteration 2);
    pos: (B,) current position.  Returns (y, k_cache, v_cache)."""
    from repro.kernels import ops
    if window < 0:
        window = cfg.sliding_window
    q, k, v = qkv_project(cfg, p, x, pos[:, None])
    s_max = k_cache.shape[2]
    slot = (pos % s_max) if window > 0 else pos          # ring buffer for SWA
    # mask-select write (one fused pass over the cache) instead of an
    # advanced-indexing scatter, whose lowering materializes transpose +
    # copy chains of the full cache (§Perf iteration 2c)
    hit = (jnp.arange(s_max)[None, :] == slot[:, None])[:, None, :, None]
    k_cache = jnp.where(hit, k[:, 0][:, :, None, :].astype(k_cache.dtype),
                        k_cache)
    v_cache = jnp.where(hit, v[:, 0][:, :, None, :].astype(v_cache.dtype),
                        v_cache)
    o = ops.decode_attention(q[:, 0], k_cache, v_cache, pos, window=window)
    y = attn_out(cfg, p, o[:, None])
    return y, k_cache, v_cache


def attention_decode_paged(cfg, p: PyTree, x: jax.Array, pos: jax.Array,
                           kv_pages: jax.Array, layer: jax.Array,
                           tables: jax.Array):
    """Single-token decode through a block-paged KV pool.

    x: (B, 1, D); kv_pages: (L, P, K, pt, 2 dh) the stacked physical
    pools shared across the batch, K and V of a page side by side in
    lanes; layer: this layer's index into L; tables: (B, NP) int32 page
    ids per row; pos: (B,) current position.  Row b's token is written
    at physical page ``tables[b, pos // pt]``, row ``pos % pt``, in
    place: the write reads and rewrites B pages, not the pool.  Inactive
    batch rows carry tables full of the scratch page id, so their writes
    land in scratch and their (discarded) outputs attend only scratch
    garbage.  Distinct live rows never share a write page (each owns
    its own).  Returns (y, kv_pages).
    """
    from repro.kernels import ops
    q, k, v = qkv_project(cfg, p, x, pos[:, None])
    pt = kv_pages.shape[3]
    pg = jnp.take_along_axis(tables, (pos // pt)[:, None], axis=1)[:, 0]
    kv = jnp.concatenate([k, v], axis=-1)               # (B, 1, K, 2dh)
    kv_pages = ops.kv_pages_write(kv_pages, layer, pg, pos % pt,
                                  jnp.swapaxes(kv, 1, 2))
    o = ops.decode_attention_paged(q[:, 0], kv_pages, tables, pos, layer)
    y = attn_out(cfg, p, o[:, None])
    return y, kv_pages


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_params(cfg, key: jax.Array, d_ff: Optional[int] = None) -> PyTree:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.act in ("silu", "geglu"):                     # gated: 3 matrices
        ks = jax.random.split(key, 3)
        return {"wg": dense_init(ks[0], (d, f), cfg.param_dtype),
                "wu": dense_init(ks[1], (d, f), cfg.param_dtype),
                "wd": dense_init(ks[2], (f, d), cfg.param_dtype, fan_in=f)}
    ks = jax.random.split(key, 2)
    return {"wu": dense_init(ks[0], (d, f), cfg.param_dtype),
            "wd": dense_init(ks[1], (f, d), cfg.param_dtype, fan_in=f)}


def mlp_block(cfg, p: PyTree, x: jax.Array) -> jax.Array:
    cd = cfg.compute_dtype
    if cfg.act in ("silu", "geglu"):
        g = quant.einsum("bsd,df->bsf", x, p["wg"], cd)
        u = quant.einsum("bsd,df->bsf", x, p["wu"], cd)
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(g) * u
    else:
        u = quant.einsum("bsd,df->bsf", x, p["wu"], cd)
        h = jax.nn.gelu(u)
    h = constrain(h, "batch", "seq", "ff")
    y = quant.einsum("bsf,fd->bsd", h, p["wd"], cd)
    return constrain(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# causal depthwise conv (Mamba-2 / Griffin temporal conv)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  state: Optional[jax.Array] = None):
    """Depthwise causal conv.  x: (B, S, C); kernel: (W, C);
    state: (B, W-1, C) prefix carried across calls (None -> zeros).
    Returns (y (B, S, C), new_state (B, W-1, C))."""
    B, S, C = x.shape
    if quant.is_quant(kernel):           # conv taps are tiny: dequant whole
        kernel = kernel.astype(jnp.float32)
    W = kernel.shape[0]
    if state is None:
        state = jnp.zeros((B, W - 1, C), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)            # (B, S+W-1, C)
    y = jnp.zeros((B, S, C), jnp.float32)
    for i in range(W):
        y = y + xp[:, i:i + S].astype(jnp.float32) \
            * kernel[i].astype(jnp.float32)
    new_state = xp[:, -(W - 1):] if W > 1 else jnp.zeros((B, 0, C), x.dtype)
    return y.astype(x.dtype), new_state


def conv_params(key: jax.Array, width: int, channels: int, dtype) -> jax.Array:
    return dense_init(key, (width, channels), dtype, fan_in=width)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_params(cfg, key: jax.Array) -> PyTree:
    return {"tok": embed_init(key, (cfg.vocab_size, cfg.d_model),
                              cfg.param_dtype)}


def embed_lookup(cfg, p: PyTree, tokens: jax.Array) -> jax.Array:
    x = quant.gather_rows(p["tok"], tokens, cfg.compute_dtype)
    return constrain(x, "batch", "seq", "embed")


def head_params(cfg, key: jax.Array) -> PyTree:
    if cfg.tie_embeddings:
        return {}
    return {"w": dense_init(key, (cfg.d_model, cfg.vocab_size),
                            cfg.param_dtype)}


def head_logits(cfg, params: PyTree, x: jax.Array) -> jax.Array:
    cd = cfg.compute_dtype
    if cfg.tie_embeddings:
        # tied head contracts the table's *scaled* axis: not a
        # per-column-scale matmul — dequant fallback
        w = params["embed"]["tok"].astype(cd).T
        logits = jnp.einsum("bsd,dv->bsv", x, w)
    else:
        logits = quant.einsum("bsd,dv->bsv", x, params["head"]["w"], cd)
    logits = constrain(logits, "batch", "seq", "vocab")
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits
