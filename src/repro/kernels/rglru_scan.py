"""RG-LRU linear recurrence (Griffin / RecurrentGemma) as a Pallas kernel.

``h_t = a_t * h_{t-1} + b_t`` elementwise over the width dim.  The
sequence is tiled into chunks (grid innermost dim, sequential); the
carried state lives in VMEM scratch.  Within a chunk the recurrence is a
``fori_loop`` over time steps, fully vectorized across the width lanes —
a pure VPU workload (no MXU), bound by the HBM stream of a and b.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, y_ref, h_scr, *, bc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bc, step, h_scr[0])
    h_scr[0] = h


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, bc: int = 256,
               interpret: bool = False) -> jax.Array:
    """a, b: (B, S, W).  Returns h at every step, (B, S, W)."""
    B, S, W = a.shape
    bc = min(bc, S)
    assert S % bc == 0
    nc = S // bc

    kern = functools.partial(_kernel, bc=bc)
    return pl.pallas_call(
        kern,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, bc, W), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, bc, W), lambda i, c: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, W), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
