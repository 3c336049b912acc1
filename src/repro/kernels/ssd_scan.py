"""Mamba-2 SSD (state-space duality) as a chunked Pallas TPU kernel.

The SSD decomposition splits the sequence into chunks of ``bc`` steps:

  * intra-chunk: a (bc x bc) lower-triangular "attention-like" matmul
    ``(C B^T ⊙ L) (dt·x)`` — quadratic only within the chunk, runs on the
    MXU;
  * inter-chunk: a rank-N state ``h`` (dp x N) carried sequentially across
    chunks in VMEM scratch — ``y += (C ⊙ decay) h_prev`` and
    ``h = decay_total·h_prev + B^T (dt·x ⊙ decay_rem)``.

Grid = (B, n_heads, n_chunks) with chunks innermost (sequential), so the
state scratch persists across the chunk dimension and is reset at c == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(A_ref, x_ref, dt_ref, B_ref, C_ref, y_ref, h_scr, *, bc: int):
    h = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_h = A_ref[h]                                       # scalar, negative
    dt = dt_ref[0, 0].astype(jnp.float32)                # (bc,)
    x = x_ref[0, 0].astype(jnp.float32)                  # (bc, dp)
    Bm = B_ref[0].astype(jnp.float32)                    # (bc, N)
    Cm = C_ref[0].astype(jnp.float32)                    # (bc, N)

    da = dt * a_h                                        # (bc,)
    cum = jnp.cumsum(da)                                 # (bc,) inclusive
    # L[i, j] = exp(cum_i - cum_j) for i >= j else 0   (segment-sum matrix)
    li = jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 1)
    diff = cum[:, None] - cum[None, :] + da[None, :]     # exclusive at j
    L = jnp.where(li >= lj, jnp.exp(diff - da[None, :]), 0.0)

    xd = x * dt[:, None]                                 # (bc, dp)

    # intra-chunk quadratic part
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (bc, bc)
    y_intra = jax.lax.dot_general(cb * L, xd, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state h_prev (dp, N)
    c_dec = Cm * jnp.exp(cum)[:, None]                   # (bc, N)
    y_inter = jax.lax.dot_general(c_dec, h_scr[...],
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(sum da) h_prev + sum_t decay_rem_t * xd_t B_t^T
    total = jnp.exp(cum[-1])
    rem = jnp.exp(cum[-1] - cum)                         # (bc,)
    xw = xd * rem[:, None]                               # (bc, dp)
    upd = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (dp, N)
    h_scr[...] = h_scr[...] * total + upd


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, bc: int = 128,
             interpret: bool = False) -> jax.Array:
    """Chunked SSD.  Shapes as in :func:`repro.kernels.ref.ssd`:

    x (b, nh, S, dp); dt (b, nh, S) positive; A (nh,) negative;
    B, C (b, S, N).  Returns y (b, nh, S, dp).
    """
    b, nh, S, dp = x.shape
    N = B.shape[-1]
    bc = min(bc, S)
    assert S % bc == 0
    nc = S // bc

    grid = (b, nh, nc)
    kern = functools.partial(_kernel, bc=bc)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bc, dp), lambda i, h, c, _: (i, h, c, 0)),
                pl.BlockSpec((1, 1, bc), lambda i, h, c, _: (i, h, c)),
                pl.BlockSpec((1, bc, N), lambda i, h, c, _: (i, c, 0)),
                pl.BlockSpec((1, bc, N), lambda i, h, c, _: (i, c, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bc, dp),
                                   lambda i, h, c, _: (i, h, c, 0)),
            scratch_shapes=[pltpu.VMEM((dp, N), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nh, S, dp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt, B, C)
