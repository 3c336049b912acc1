"""Weight transform: the compute phase of the paper's decoupled weight
application, fused into one Pallas kernel.

Cicada splits weight loading into I/O-bound *file retrieval* and
compute-bound *weight application*.  On TPU the application phase is a
dtype/layout transform ahead of the host->HBM DMA: dequantize int8
extents (per-output-channel scales) or cast f32 extents to the serving
dtype.  Fusing it keeps application off the critical path — one pass over
the weight bytes, tiled (bn x bm) to stay inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_kernel(w_ref, s_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)          # (1, bm)
    o_ref[...] = (w * s).astype(o_ref.dtype)


def _cast_kernel(w_ref, o_ref):
    o_ref[...] = w_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "bn", "bm", "interpret"))
def weight_transform(w: jax.Array, scale=None, *, out_dtype=jnp.bfloat16,
                     bn: int = 256, bm: int = 512,
                     interpret: bool = False) -> jax.Array:
    """w: (n, m) int8 (with scale (m,)) or float (scale None). -> (n, m)."""
    n, m = w.shape
    bn = min(bn, n)
    bm = min(bm, m)
    # pad to tile multiples (weight extents are arbitrary shapes)
    pn = (-n) % bn
    pm = (-m) % bm
    wp = jnp.pad(w, ((0, pn), (0, pm))) if (pn or pm) else w
    N, M = wp.shape
    grid = (N // bn, M // bm)

    if scale is not None:
        sp = jnp.pad(scale, (0, pm)) if pm else scale
        out = pl.pallas_call(
            _dequant_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
                pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((N, M), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(wp, sp[None, :])
    else:
        out = pl.pallas_call(
            _cast_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bn, bm), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((N, M), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(wp)
    if pn or pm:
        out = out[:n, :m]
    return out
