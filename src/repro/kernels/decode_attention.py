"""Single-token decode attention as a Pallas TPU kernel.

Decode is memory-bound: one query token must stream the whole KV cache
from HBM.  The kernel tiles the cache sequence (split-K) with the grid's
innermost dimension and merges partial softmax statistics in VMEM
scratch, processing all ``q_rep`` query heads of one KV head together so
each K/V block is read exactly once (GQA-aware).

Ring-buffer sliding-window caches are supported: slot ``j`` of a cache
with ``S_max == window`` holds absolute position ``p`` where
``p ≡ j (mod window)``; validity is derived in-kernel from ``pos``.

:func:`decode_attention_paged` is the block-paged variant, a kernel of
its own: K/V live in a shared physical pool of fixed-size pages, one
per layer stacked into ``(L, n_pages, K, pt, 2 * dh)`` with a page's
keys in lanes ``[0, dh)`` and its values in ``[dh, 2 * dh)`` (lane-dense:
at a head size of 64 one 128-lane tile per head and page, so the pool's
default device layout is row-major, the kernels' own), and each batch
row owns a *page table* mapping logical page j to a physical page id.
The layer index rides scalar prefetch, so the kernel reads pages
straight out of the stacked pool.  Its work follows each row's ``pos``,
not the table width: a work list built before the call gives one grid
step per chunk of a row's live pages (``pos // pt + 1``, or every page
once a ring has wrapped), rows in order, so the grid itself is as long
as the live pages.  Each step fetches whole pages, all KV heads at once,
through one BlockSpec per page of the chunk (the table and the work
list ride scalar prefetch), and the pipeline fetches step i+1's pages
while step i computes.  A slot of the chunk past the live count points
at page 0, so a run of short rows fetches it once.  The body scores
every query head against every key of the chunk with two matmuls, masks
keys of other KV heads and positions past ``pos`` (or outside the ring
window), and keeps the online softmax in float32 per query head.  Where
the head size is a multiple of 128 lanes, K and V are static lane slices
of the page; otherwise the query comes zero-padded to ``2 * dh`` lanes,
so the scores see only K, and ``p . kv`` carries ``p . v`` in the upper
lanes, which the wrapper keeps.  Table entries past the live pages are
never used.

:func:`kv_pages_write` writes into the stacked pool in place (the pool
is aliased to its output): a decode step's new row of each batch row,
reading and writing back B pages, or a pack's whole pages.
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


def _live_pages(pos, pt: int, n_tbl: int, window: int):
    """Logical pages of a row that hold a key position ``pos`` attends
    to: ``[0, pos]`` for a full cache; every page once a ring wraps."""
    n = jnp.minimum(pos // pt + 1, n_tbl)
    if window > 0:
        n = jnp.where(pos >= window, n_tbl, n)
    return n


def _paged_kernel(layer_ref, pos_ref, tbl_ref, work_ref, q_ref, *refs,
                  window: int, n_tbl: int, chunk: int, rep: int, dh: int,
                  scale: float):
    kv_refs = refs[:chunk]
    o_ref, m_scr, l_scr, acc_scr = refs[chunk:]
    _, _, K, pt, w = kv_refs[0].shape                        # w = 2 * dh
    H = K * rep
    s_id = work_ref[pl.program_id(0)]
    b, c0 = s_id // n_tbl, s_id % n_tbl   # row, logical index of 1st page
    pos = pos_ref[b]

    @pl.when(c0 == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # every query head against every key of the chunk's pages, keys of
    # other KV heads masked out: two matmuls a step, whatever K is
    blocks = [r[0, 0].astype(jnp.float32).reshape(K * pt, w)
              for r in kv_refs]
    kv = blocks[0] if chunk == 1 else jnp.concatenate(blocks, axis=0)
    if dh % LANES == 0:
        # K and V are whole lane tiles of the page: static slices
        k, v = kv[:, :dh], kv[:, dh:]
    else:
        # the query is zero in V's lanes, so q . kv^T == q . k^T, and
        # p . kv carries p . v in lanes [dh, 2 dh), which the caller
        # keeps: no lane split inside the kernel
        k = v = kv
    q = q_ref[0].astype(jnp.float32)
    n = chunk * K * pt
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale                                             # (H, n)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, n), 0) // rep
    col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)
    in_page = col % (K * pt)
    # a page past the live count holds another page's keys: its logical
    # positions lie past pos, so the mask drops it like any dead slot
    idx = (c0 + col // (K * pt)) * pt + in_page % pt
    if window > 0:
        p_at = pos - ((pos - idx) % window)
        valid = jnp.logical_and(p_at >= 0, p_at > pos - window)
    else:
        valid = idx <= pos
    valid = jnp.logical_and(valid, in_page // pt == head)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(c0 + chunk >= _live_pages(pos, pt, n_tbl, window))
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "bs", "interpret"))
def decode_attention_paged(q: jax.Array, kv_pages: jax.Array,
                           tables: jax.Array, pos: jax.Array,
                           layer: jax.Array, *, window: int = 0,
                           bs: int = 128, interpret: bool = False
                           ) -> jax.Array:
    """q: (B, H, dh); kv_pages: (L, P, K, pt, 2 dh) the stacked page
    pools of L layers, K in lanes [0, dh) and V in [dh, 2 dh) of each
    page, shared across the batch; tables: (B, NP) int32 physical page
    ids (logical sequence extent NP * pt per row); pos: (B,); layer: the
    index into L whose pages are read.  Returns (B, H, dh).

    The grid has one step per chunk of a row's live pages, rows in
    order; a chunk is ``bs // (K * pt)`` pages (at least one), so a step
    holds about ``bs`` keys over all KV heads.  Ring-window semantics
    are the slotted kernel's over the logical extent.
    """
    B, H, dh = q.shape
    K, pt, w = kv_pages.shape[2], kv_pages.shape[3], kv_pages.shape[4]
    assert w == 2 * dh, (kv_pages.shape, q.shape)
    NP = tables.shape[1]
    chunk = max(1, bs // (K * pt))
    pos = pos.astype(jnp.int32)
    split = dh % LANES == 0
    if not split:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dh)))      # zero in V's lanes
    dq = q.shape[-1]
    # the work list: step s covers row b's pages [c0, c0 + chunk), for
    # work[s] = b * NP + c0; row b takes its steps after row b - 1's
    n_steps = -(-_live_pages(pos, pt, NP, window) // chunk)
    ends = jnp.cumsum(n_steps)
    steps = jnp.arange(B * -(-NP // chunk), dtype=jnp.int32)
    done = ends[None, :] <= steps[:, None]               # row b is behind s
    row = jnp.minimum(jnp.sum(done, axis=1), B - 1)
    first = jnp.sum(jnp.where(done, n_steps[None, :], 0), axis=1)
    work = (row * NP + jnp.minimum((steps - first) * chunk, NP - 1)
            ).astype(jnp.int32)

    def page_spec(i):
        def index(s, layer_ref, pos_ref, tbl_ref, work_ref):
            b, j = work_ref[s] // NP, work_ref[s] % NP + i
            # a page past the live count maps to page 0, which the
            # pipeline then fetches once for a run of short rows
            live = j < _live_pages(pos_ref[b], pt, NP, window)
            return (layer_ref[0],
                    jnp.where(live, tbl_ref[b * NP + jnp.minimum(j, NP - 1)],
                              0), 0, 0, 0)
        return pl.BlockSpec((1, 1, K, pt, w), index)

    row_spec = pl.BlockSpec((1, H, dq),
                            lambda s, l, p, t, wk: (wk[s] // NP, 0, 0))
    kern = functools.partial(_paged_kernel, window=window, n_tbl=NP,
                             chunk=chunk, rep=H // K, dh=dh,
                             scale=1.0 / math.sqrt(dh))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ends[-1],),
            in_specs=[row_spec] + [page_spec(i) for i in range(chunk)],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, LANES), jnp.float32),
                pltpu.VMEM((H, dq), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dq), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos,
      tables.astype(jnp.int32).reshape(-1), work, q, *[kv_pages] * chunk)
    return out if split else out[..., dh:]


def _write_kernel(layer_ref, pg_ref, row_ref, kv_ref, page_ref, o_ref):
    if kv_ref.shape[3] == o_ref.shape[3]:                   # whole pages
        o_ref[...] = kv_ref[...]
    else:
        hit = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 3) == \
            row_ref[pl.program_id(0)]
        o_ref[...] = jnp.where(hit, kv_ref[...], page_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_pages_write(kv_pages: jax.Array, layer: jax.Array, pg: jax.Array,
                   row: jax.Array, kv: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """Write ``kv[b]`` (K, R, 2 dh), keys in lanes [0, dh) and values in
    [dh, 2 dh), into page ``pg[b]`` of layer ``layer[b]`` (or one
    ``layer`` for all) of the stacked pools ``kv_pages`` (L, P, K, pt,
    2 dh), in place: the pool is the output (aliased), and the call
    touches N pages, whatever the pool holds.  R is 1, a decode step's
    row at ``row[b]``: the page is read and written back with the row
    replaced; or pt, whole pages (a pack), written without a read and
    ``row`` unused.  Writes that share a page (a decode step's dead rows
    parked on the scratch page) leave one writer's row there, or none.
    """
    N, K, R, w = kv.shape
    pt = kv_pages.shape[3]
    assert R in (1, pt), (kv.shape, kv_pages.shape)
    layer = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (N,))
    page = pl.BlockSpec((1, 1, K, pt, w),
                        lambda b, l, g, r: (l[b], g[b], 0, 0, 0))
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N,),
            in_specs=[pl.BlockSpec((1, 1, K, R, w),
                                   lambda b, l, g, r: (0, b, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY) if R == pt
                      else page],
            out_specs=page,
        ),
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, pg.astype(jnp.int32), row.astype(jnp.int32),
      kv.astype(kv_pages.dtype)[None], kv_pages)
