"""Flash attention (prefill / training) as a Pallas TPU kernel.

Online-softmax tiling: grid = (B, H, nQ, nK) with the KV dimension
innermost (sequential on TPU, so VMEM scratch carries running statistics
across KV blocks).  Supports causal masking, sliding windows and GQA
(every q head reads its kv head via the BlockSpec index map — no
materialized ``jnp.repeat``).

Block sizes are MXU-aligned (multiples of 128 on the contraction/lane
dims).  Fully-masked KV blocks are skipped with ``pl.when`` — on real
hardware this prunes ~half the work for causal prefill and all but
ceil(window/bk)+1 blocks per q row for sliding windows.

Sequence lengths need not be tile multiples: queries and keys are
padded at the end up to whole tiles, padded keys are masked out and
padded query rows are sliced off.
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


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, bq: int, bk: int, nk: int,
            q_offset: int, kv_len: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global coordinates of this tile; queries sit at the *end* of the key
    # sequence when q_offset > 0 (chunked prefill).
    q_lo = qi * bq + q_offset
    k_lo = ki * bk

    run = True
    if causal:
        run = k_lo <= q_lo + bq - 1                     # not above diagonal
    if window > 0:
        run = jnp.logical_and(run, k_lo + bk - 1 > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)             # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                   # (bq, bk)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len                            # padded keys
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
            if window > 0:
                mask = jnp.logical_and(mask, kpos > qpos - window)
        elif window > 0:
            mask = jnp.logical_and(mask, jnp.abs(kpos - qpos) < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                           # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 256, bk: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, dh); k, v: (B, K, T, dh).  Returns (B, H, S, dh).

    When T > S (chunked prefill against an existing prefix) queries are
    the last S positions of the key sequence.  Tiles shrink to the
    8-aligned sequence length; S and T are padded up to whole tiles.
    """
    B, H, S, dh = q.shape
    K, T = k.shape[1], k.shape[2]
    assert H % K == 0 and k.shape == v.shape
    rep = H // K
    q_offset = T - S                  # from the unpadded lengths
    bq = min(bq, _round_up(S, SUBLANES))
    bk = min(bk, _round_up(T, SUBLANES))
    Sp, Tp = _round_up(S, bq), _round_up(T, bk)
    if Sp > S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Tp > T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    nq, nk = Sp // bq, Tp // bk

    grid = (B, H, nq, nk)
    kern = functools.partial(
        _kernel, causal=causal, window=window, bq=bq, bk=bk, nk=nk,
        q_offset=q_offset, kv_len=T, scale=1.0 / math.sqrt(dh))

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j, _rep=rep: (b, h // _rep, j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, i, j, _rep=rep: (b, h // _rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max
            pltpu.VMEM((bq, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S] if Sp > S else out
