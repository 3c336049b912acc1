"""Pure-jnp oracles for every Pallas kernel.

These are the *semantic definitions*: small, obviously-correct, O(S^2)
memory where that is the honest definition.  Kernel tests sweep shapes and
dtypes and assert the Pallas (interpret-mode) output matches these within
dtype tolerance.  ``ops.py`` never calls these on the hot path — it has its
own memory-efficient XLA fallbacks — except where the oracle *is* already
the efficient form.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def mha_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0) -> jax.Array:
    """Naive full-materialization attention.

    q: (B, H, S, dh); k, v: (B, K, T, dh) with H a multiple of K (GQA).
    window: 0 -> full; >0 -> sliding window of that many positions
    (a query at i attends to keys in (i-window, i]).
    Returns (B, H, S, dh), same dtype as q.
    """
    B, H, S, dh = q.shape
    K, T = k.shape[1], k.shape[2]
    rep = H // K
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=1)
    scores = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / jnp.sqrt(dh)
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        # queries are the *last* S positions of the T-long key sequence
        offs = T - S
        mask &= ki <= (qi + offs)
        if window > 0:
            mask &= ki > (qi + offs - window)
    elif window > 0:
        mask &= jnp.abs(ki - qi) < window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", p, vf)
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int = 0) -> jax.Array:
    """One-token decode against a (possibly ring-buffered) KV cache.

    q: (B, H, dh) — the single new query (already rotated).
    k_cache/v_cache: (B, K, S_max, dh) — kv-head-major layout (dh is the
         contraction minor dim for both attention dots, so no transpose
         is ever materialized; see §Perf iteration 2).  Keys are rotated
         at write time.
    pos: (B,) int32 — index of the *current* token (the one q belongs to);
         its K/V entry is already in the cache.
    window: 0 -> full cache, valid slots are [0, pos]; >0 -> cache is a ring
         buffer of S_max == window slots, slot j holds some absolute position
         p with p % window == j; valid iff p in (pos-window, pos].
    Returns (B, H, dh).
    """
    B, H, dh = q.shape
    K, S_max = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    # dots consume the cache in its stored dtype and accumulate f32
    # (MXU semantics) — no materialized f32 copy of the cache
    qr = q.reshape(B, K, rep, dh)
    scores = jnp.einsum("bkrd,bksd->bkrs", qr, k_cache,
                        preferred_element_type=jnp.float32) / jnp.sqrt(dh)
    idx = jnp.arange(S_max)[None, :]                      # (1, S)
    if window > 0:
        # ring buffer: slot j valid iff the position it holds is within the
        # window.  Slot j holds position p where p = largest value <= pos
        # with p % window == j.
        cur = pos[:, None]
        p_at_slot = cur - ((cur - idx) % window)
        valid = (p_at_slot >= 0) & (p_at_slot > cur - window)
    else:
        valid = idx <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrs,bksd->bkrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, dh).astype(q.dtype)


def decode_attention_paged(q: jax.Array, kv_pages: jax.Array,
                           tables: jax.Array, pos: jax.Array,
                           layer: jax.Array, *, window: int = 0
                           ) -> jax.Array:
    """Block-paged decode attention: gather-then-attend oracle.

    q: (B, H, dh); kv_pages: (L, P, K, pt, 2 dh) — the stacked physical
    page pools of L layers, K in lanes [0, dh) and V in [dh, 2 dh),
    shared across the batch; tables: (B, NP) int32 page ids per row
    (logical extent NP * pt); pos: (B,); layer: which of the L pools.

    The definition: per batch row, gather its NP pages into the
    contiguous logical cache and run :func:`decode_attention` — so the
    math (masking of garbage rows beyond ``pos``, ring-window validity
    over logical positions, GQA) is *identical* to the slotted oracle.
    """
    B, dh = q.shape[0], q.shape[2]
    K, pt = kv_pages.shape[2], kv_pages.shape[3]
    NP = tables.shape[1]
    kv = jnp.swapaxes(kv_pages[layer, tables], 1, 2).reshape(
        B, K, NP * pt, 2 * dh)                          # (B, K, NP*pt, 2dh)
    return decode_attention(q, kv[..., :dh], kv[..., dh:], pos,
                            window=window)


def kv_pages_write(kv_pages: jax.Array, layer: jax.Array, pg: jax.Array,
                   row: jax.Array, kv: jax.Array) -> jax.Array:
    """``kv[b]`` (K, R, 2 dh) into page ``pg[b]`` of pool ``layer[b]``
    (or one ``layer``) of ``kv_pages`` (L, P, K, pt, 2 dh): at row
    ``row[b]`` where R is 1, as the whole page where R is pt.  Writes
    that share a page keep an arbitrary writer."""
    kv = kv.astype(kv_pages.dtype)
    if kv.shape[2] == kv_pages.shape[3]:
        return kv_pages.at[layer, pg].set(kv)
    return kv_pages.at[layer, pg, :, row].set(kv[:, :, 0])


# ---------------------------------------------------------------------------
# Mamba-2 SSD (state-space duality)
# ---------------------------------------------------------------------------

def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, *, h0: Optional[jax.Array] = None,
        return_state: bool = False):
    """Naive sequential SSD recurrence (the definition).

    x:  (b, nh, S, dp)   inputs per head
    dt: (b, nh, S)       positive step sizes (softplus already applied)
    A:  (nh,)            negative decay rates (A = -exp(A_log))
    B:  (b, S, N)        input projections (ngroups=1, shared over heads)
    C:  (b, S, N)        output projections
    h0: (b, nh, dp, N)   optional initial state
    Recurrence per head:  h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T
                          y_t = h_t C_t + D x_t   (D skip applied by caller)
    Returns y (b, nh, S, dp) [, h_S (b, nh, dp, N)].
    """
    b, nh, S, dp = x.shape
    N = B.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, nh, dp, N), jnp.float32)

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)

    def step(h, t):
        decay = jnp.exp(dtf[:, :, t] * Af[None, :])            # (b, nh)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dtf[:, :, t], xf[:, :, t], Bf[:, t])
        h = h * decay[:, :, None, None] + upd
        y_t = jnp.einsum("bhpn,bn->bhp", h, Cf[:, t])
        return h, y_t

    hS, ys = jax.lax.scan(step, h0, jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 2).astype(x.dtype)                  # (b, nh, S, dp)
    if return_state:
        return y, hS
    return y


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence (Griffin / RecurrentGemma)
# ---------------------------------------------------------------------------

def rglru(a: jax.Array, b: jax.Array, *, h0: Optional[jax.Array] = None,
          return_state: bool = False):
    """h_t = a_t * h_{t-1} + b_t, elementwise over the width dim.

    a, b: (B, S, W); h0: (B, W).  Returns h at every step (B, S, W).
    (Gate computation — r_t, i_t, the sqrt(1-a^2) input scale — happens in
    the model; the kernel is the pure first-order linear recurrence.)
    """
    B, S, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def step(h, t):
        h = af[:, t] * h + bf[:, t]
        return h, h

    hS, ys = jax.lax.scan(step, h0, jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1).astype(a.dtype)
    if return_state:
        return y, hS
    return y


# ---------------------------------------------------------------------------
# weight transform (the paper's weight-application compute phase)
# ---------------------------------------------------------------------------

def weight_transform(w: jax.Array, scale: Optional[jax.Array], out_dtype
                     ) -> jax.Array:
    """Dequantize / cast a stored weight to its compute representation.

    w: (n, m) int8 (quantized, with per-column f32 `scale` (m,)) or any
    float dtype (scale is None -> pure cast).
    """
    if scale is not None:
        return (w.astype(jnp.float32) * scale[None, :].astype(jnp.float32)
                ).astype(out_dtype)
    return w.astype(out_dtype)


# ---------------------------------------------------------------------------
# quant matmul (w8a16: int8-resident weights, dequant fused at compute)
# ---------------------------------------------------------------------------

def quant_matmul(x: jax.Array, w: jax.Array, scale: jax.Array,
                 out_dtype=None) -> jax.Array:
    """Dequant-then-matmul — the semantic definition the fused kernel
    must match: materialize the f32 weight exactly as the dequant-at-
    load path does (``weight_transform``), then contract in f32.

    x: (m, k) activations (any float dtype); w: (k, n) int8;
    scale: (n,) f32 per-column.  Returns (m, n) in ``out_dtype``
    (default: x.dtype).
    """
    wf = w.astype(jnp.float32) * scale[None, :].astype(jnp.float32)
    out = jnp.dot(x.astype(jnp.float32), wf,
                  preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)
