"""Fused-dequant w8a16 matmul as a Pallas TPU kernel.

Decode is memory-bound on weights: every step streams each weight
matrix from HBM once.  Serving int8 weights in place halves that
traffic — the kernel reads int8 tiles plus per-column f32 scales,
upcasts *in-register* (``w.astype(f32) * scale``) and feeds the MXU
directly, so no dequantized copy ever exists in HBM or VMEM beyond the
current tile.

Grid is (M-tiles, N-tiles, K-tiles) with K innermost ("arbitrary"):
partial products accumulate into an f32 VMEM scratch and flush to the
output block on the last K step — the same scratch-merge idiom as the
decode-attention split-K kernel.  Non-divisible shapes are padded up to
the tile grid and sliced back (zero K padding contributes zero to the
accumulator).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)                   # (bm, bk)
    w = w_ref[...].astype(jnp.float32)                   # (bk, bn)
    s = s_ref[...].astype(jnp.float32)                   # (1, bn)
    acc_scr[...] += jax.lax.dot_general(
        x, w * s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "bm", "bk", "bn", "interpret"))
def quant_matmul(x: jax.Array, w: jax.Array, scale: jax.Array, *,
                 out_dtype=None, bm: int = 256, bk: int = 512,
                 bn: int = 256, interpret: bool = False) -> jax.Array:
    """x: (m, k) activations; w: (k, n) int8; scale: (n,) f32 per-column.

    Returns (m, n) in ``out_dtype`` (default: x.dtype), numerically the
    dequant-then-matmul reference with dequant fused per tile.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert scale.shape == (n,), (scale.shape, n)
    out_dtype = out_dtype or x.dtype
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)

    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    wp = jnp.pad(w, ((0, pk), (0, pn))) if (pk or pn) else w
    sp = jnp.pad(scale, (0, pn)) if pn else scale
    M, K = xp.shape
    N = wp.shape[1]
    nm, nn, nk = M // bm, N // bn, K // bk

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp, sp[None, :])
    if pm or pn:
        out = out[:m, :n]
    return out
