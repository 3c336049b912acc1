"""Single-token decode attention as a Pallas TPU kernel.

Decode is memory-bound: one query token must stream the whole KV cache
from HBM.  The kernel tiles the cache sequence (split-K) with the grid's
innermost dimension and merges partial softmax statistics in VMEM
scratch, processing all ``q_rep`` query heads of one KV head together so
each K/V block is read exactly once (GQA-aware).

Ring-buffer sliding-window caches are supported: slot ``j`` of a cache
with ``S_max == window`` holds absolute position ``p`` where
``p ≡ j (mod window)``; validity is derived in-kernel from ``pos``.

:func:`decode_attention_paged` is the block-paged variant: K/V live in
a shared physical pool of fixed-size pages (``(n_pages, K, pt, dh)``)
and each batch row owns a *page table* mapping logical page j to a
physical page id.  The split-K grid already tiles the cache sequence,
so paging is purely an index-map change — the table rides the scalar
prefetch channel (``num_scalar_prefetch=2``) and logical cache block
``s`` is fetched from physical block ``(table[b, s // r], s % r)``
where ``r = pt // bs``.  The kernel body (online softmax, GQA packing,
ring-window validity over *logical* positions) is shared verbatim with
the slotted kernel; unallocated table entries may point anywhere —
their positions are beyond ``pos``, so masking zeroes them exactly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
SUBLANES = 8


def seq_tile(bs: int, n: int) -> int:
    """Cache-sequence tile: ``n`` itself when it fits in ``bs``, else the
    largest 8-aligned divisor of ``n`` not above ``bs``.  The cache is
    the kernel's input on every step, so it is tiled, never padded."""
    if n <= bs:
        return n
    for t in range(bs - bs % SUBLANES, 0, -SUBLANES):
        if n % t == 0:
            return t
    raise ValueError(
        f"no {SUBLANES}-aligned tile <= {bs} divides the cache length {n}; "
        f"use a cache length that is a multiple of {SUBLANES}")


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            window: int, bs: int, ns: int, rep: int, scale: float):
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    s_lo = si * bs

    # Skip cache blocks that are entirely invalid (beyond pos for a full
    # cache; a full ring buffer has no invalid blocks).
    if window > 0:
        run = jnp.logical_or(pos >= window, s_lo <= pos)
    else:
        run = s_lo <= pos

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (rep, dh)
        k = k_ref[0, 0].astype(jnp.float32)              # (bs, dh)
        v = v_ref[0, 0].astype(jnp.float32)              # (bs, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                    # (rep, bs)

        idx = s_lo + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
        if window > 0:
            p_at = pos - ((pos - idx) % window)
            valid = jnp.logical_and(p_at >= 0, p_at > pos - window)
        else:
            valid = idx <= pos
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(si == ns - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "bs", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int = 0, bs: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, dh); caches: (B, K, S_max, dh) kv-head-major; pos: (B,).

    Returns (B, H, dh).  See module docstring for ring-buffer semantics.
    """
    B, H, dh = q.shape
    K, S_max = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    bs = seq_tile(bs, S_max)
    ns = S_max // bs

    qr = q.reshape(B, K, rep, dh)
    kc = k_cache                                         # (B, K, S, dh)
    vc = v_cache

    grid = (B, K, ns)
    kern = functools.partial(_kernel, window=window, bs=bs, ns=ns, rep=rep,
                             scale=1.0 / math.sqrt(dh))

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rep, dh), lambda b, h, s, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bs, dh), lambda b, h, s, _: (b, h, s, 0)),
                pl.BlockSpec((1, 1, bs, dh), lambda b, h, s, _: (b, h, s, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rep, dh),
                                   lambda b, h, s, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rep, LANES), jnp.float32),
                pltpu.VMEM((rep, LANES), jnp.float32),
                pltpu.VMEM((rep, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, rep, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), qr, kc, vc)
    return out.reshape(B, H, dh)


@functools.partial(
    jax.jit, static_argnames=("window", "bs", "interpret"))
def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, tables: jax.Array,
                           pos: jax.Array, *, window: int = 0,
                           bs: int = 128, interpret: bool = False
                           ) -> jax.Array:
    """q: (B, H, dh); page pools: (P, K, pt, dh) kv-head-major, shared
    across the batch; tables: (B, NP) int32 physical page ids (logical
    sequence extent NP * pt per row); pos: (B,).  Returns (B, H, dh).

    The tile divides ``pt`` (:func:`seq_tile`) so every grid block
    lives inside one page.  Ring-window semantics are identical to the
    slotted kernel over the logical extent.
    """
    B, H, dh = q.shape
    K, pt = k_pages.shape[1], k_pages.shape[2]
    NP = tables.shape[1]
    rep = H // K
    bs = seq_tile(bs, pt)
    r = pt // bs                     # cache blocks per page
    ns = NP * r

    qr = q.reshape(B, K, rep, dh)
    grid = (B, K, ns)
    # the body is the slotted kernel's: s_lo = si * bs is the *logical*
    # offset of block si, which the shared masking math consumes; only
    # the fetch location below goes through the page table
    kern = functools.partial(_kernel, window=window, bs=bs, ns=ns, rep=rep,
                             scale=1.0 / math.sqrt(dh))

    def paged_kern(pos_ref, tbl_ref, *rest):
        del tbl_ref                  # consumed by the index maps only
        kern(pos_ref, *rest)

    def kv_map(b, h, s, pos_ref, tbl_ref):
        del pos_ref
        return (tbl_ref[b, s // r], h, s % r, 0)

    out = pl.pallas_call(
        paged_kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rep, dh),
                             lambda b, h, s, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bs, dh), kv_map),
                pl.BlockSpec((1, 1, bs, dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, rep, dh),
                                   lambda b, h, s, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rep, LANES), jnp.float32),
                pltpu.VMEM((rep, LANES), jnp.float32),
                pltpu.VMEM((rep, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, rep, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), tables.astype(jnp.int32), qr, k_pages, v_pages)
    return out.reshape(B, H, dh)
