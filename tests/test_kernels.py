"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and dtypes, plus the XLA fallbacks against the same
oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention as decode_pallas
from repro.kernels.decode_attention import (decode_attention_paged
                                            as paged_pallas)
from repro.kernels.flash_attention import flash_attention as flash_pallas
from repro.kernels.rglru_scan import rglru_scan as rglru_pallas
from repro.kernels.ssd_scan import ssd_scan as ssd_pallas
from repro.kernels.weight_transform import weight_transform as wt_pallas

R = np.random.default_rng(0)


def arr(*s, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(R.standard_normal(s) * scale, dtype)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,S,dh", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA 4x
    (1, 3, 1, 128, 32),     # MQA, odd heads
])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (True, 64), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(B, H, K, S, dh, causal, window, dtype):
    q, k, v = arr(B, H, S, dh, dtype=dtype), arr(B, K, S, dh, dtype=dtype), \
        arr(B, K, S, dh, dtype=dtype)
    o_ref = ref.mha_attention(q, k, v, causal=causal, window=window)
    o_pal = flash_pallas(q, k, v, causal=causal, window=window,
                         bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), **tol(dtype))
    o_xla = ops._xla_flash(q, k, v, causal=causal, window=window, bk=64)
    np.testing.assert_allclose(np.asarray(o_xla, np.float32),
                               np.asarray(o_ref, np.float32), **tol(dtype))


def test_flash_chunked_prefill():
    """T > S: queries are the last S positions (prefix continuation)."""
    B, H, K, S, T, dh = 1, 4, 2, 64, 192, 32
    q = arr(B, H, S, dh)
    k, v = arr(B, K, T, dh), arr(B, K, T, dh)
    o_ref = ref.mha_attention(q, k, v, causal=True, window=0)
    o_pal = flash_pallas(q, k, v, causal=True, window=0, bq=32, bk=32,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_block_shape_sweep():
    B, H, K, S, dh = 1, 2, 2, 256, 64
    q, k, v = arr(B, H, S, dh), arr(B, K, S, dh), arr(B, K, S, dh)
    o_ref = ref.mha_attention(q, k, v, causal=True)
    for bq, bk in [(32, 128), (128, 32), (256, 256), (64, 64)]:
        o = flash_pallas(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_vs_ref(window, dtype):
    B, H, K, dh, S = 3, 8, 2, 64, 128
    q = arr(B, H, dh, dtype=dtype)
    kc, vc = arr(B, K, S, dh, dtype=dtype), arr(B, K, S, dh, dtype=dtype)
    pos = jnp.asarray([3, 100, 127], jnp.int32)
    o_ref = ref.decode_attention(q, kc, vc, pos, window=window)
    o_pal = decode_pallas(q, kc, vc, pos, window=window, bs=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), **tol(dtype))


def test_decode_matches_full_attention():
    """Decode over a cache == last row of full causal attention."""
    B, H, K, dh, S = 2, 4, 2, 32, 96
    q_all = arr(B, H, S, dh)
    k_all, v_all = arr(B, K, S, dh), arr(B, K, S, dh)
    full = ref.mha_attention(q_all, k_all, v_all, causal=True)
    pos = jnp.full((B,), S - 1, jnp.int32)
    dec = ref.decode_attention(q_all[:, :, -1], k_all, v_all, pos)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, :, -1]),
                               atol=1e-5, rtol=1e-5)


def test_decode_ring_buffer_semantics():
    """A full ring cache attends to exactly the last `window` positions."""
    B, H, K, dh, W = 1, 2, 1, 16, 32
    pos_val = 100                          # cache wrapped 3+ times
    keys = arr(B, K, W, dh)
    vals = arr(B, K, W, dh)
    q = arr(B, H, dh)
    pos = jnp.asarray([pos_val], jnp.int32)
    out = ref.decode_attention(q, keys, vals, pos, window=W)
    # oracle: arrange the W entries by absolute position and attend to all
    o_pal = decode_pallas(q, keys, vals, pos, window=W, bs=8, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(out),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

def _paged_from_slotted(kc, vc, NP, pt, n_garbage=0):
    """Scatter a (B, K, S, dh) slotted cache into page pools + tables;
    page order is deliberately shuffled.  ``n_garbage`` extra table
    columns point at an arbitrary live page (rows beyond the logical
    extent, whose masking must zero them exactly)."""
    B, K, S, dh = kc.shape
    assert S == NP * pt
    P = B * NP + 2                         # two never-referenced pages
    perm = np.random.default_rng(7).permutation(B * NP)
    tables = np.full((B, NP + n_garbage), perm[0], np.int32)
    k_pages = np.array(arr(P, K, pt, dh, dtype=kc.dtype))  # garbage fill
    v_pages = np.array(arr(P, K, pt, dh, dtype=vc.dtype))
    for b in range(B):
        for j in range(NP):
            pid = int(perm[b * NP + j])
            tables[b, j] = pid
            k_pages[pid] = np.asarray(kc[b, :, j * pt:(j + 1) * pt])
            v_pages[pid] = np.asarray(vc[b, :, j * pt:(j + 1) * pt])
    return (jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables))


@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("pt,bs", [(32, 32), (32, 16), (64, 32)])
def test_decode_paged_vs_slotted(window, pt, bs):
    """Paged kernel == slotted kernel on the same logical cache, across
    divisible and sub-page tile sizes."""
    B, H, K, dh, S = 3, 8, 2, 64, 128
    q = arr(B, H, dh)
    kc, vc = arr(B, K, S, dh), arr(B, K, S, dh)
    pos = jnp.asarray([3, 100, 127], jnp.int32)
    o_slot = ref.decode_attention(q, kc, vc, pos, window=window)
    k_pages, v_pages, tables = _paged_from_slotted(kc, vc, S // pt, pt)
    o_ref = ref.decode_attention_paged(q, k_pages, v_pages, tables, pos,
                                       window=window)
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_slot))
    o_pal = paged_pallas(q, k_pages, v_pages, tables, pos, window=window,
                         bs=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_slot),
                               atol=2e-5, rtol=2e-5)


def test_decode_paged_ragged_tables():
    """Table columns beyond the logical extent hold arbitrary page ids:
    their positions exceed ``pos`` so masking must zero them exactly."""
    B, H, K, dh, S, pt = 2, 4, 2, 32, 64, 16
    q = arr(B, H, dh)
    kc, vc = arr(B, K, S, dh), arr(B, K, S, dh)
    pos = jnp.asarray([10, 63], jnp.int32)
    o_slot = ref.decode_attention(q, kc, vc, pos)
    k_pages, v_pages, tables = _paged_from_slotted(kc, vc, S // pt, pt,
                                                   n_garbage=2)
    o_ref = ref.decode_attention_paged(q, k_pages, v_pages, tables, pos)
    # the slotted oracle over the same extent, with different garbage in
    # the two extra pages: exact equality means masking zeroes them
    # exactly (the softmax reduces over the same length in both)
    kx = jnp.concatenate([kc, arr(B, K, 2 * pt, dh)], axis=2)
    vx = jnp.concatenate([vc, arr(B, K, 2 * pt, dh)], axis=2)
    o_ext = ref.decode_attention(q, kx, vx, pos)
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_ext))
    o_pal = paged_pallas(q, k_pages, v_pages, tables, pos, bs=16,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_slot),
                               atol=2e-5, rtol=2e-5)


def test_decode_paged_ops_dispatch():
    """ops.decode_attention_paged (registry wrapper) matches the oracle
    in whatever mode this environment resolved."""
    B, H, K, dh, S, pt = 2, 4, 2, 32, 64, 16
    q = arr(B, H, dh)
    kc, vc = arr(B, K, S, dh), arr(B, K, S, dh)
    pos = jnp.asarray([7, 60], jnp.int32)
    k_pages, v_pages, tables = _paged_from_slotted(kc, vc, S // pt, pt)
    o_ref = ref.decode_attention_paged(q, k_pages, v_pages, tables, pos)
    o = ops.decode_attention_paged(q, k_pages, v_pages, tables, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,nh,S,dp,N,bc", [
    (1, 2, 64, 16, 32, 16),
    (2, 3, 128, 32, 64, 64),
    (1, 1, 96, 16, 16, 32),    # S not a multiple of 2*bc
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_vs_ref(b, nh, S, dp, N, bc, dtype):
    x = arr(b, nh, S, dp, dtype=dtype)
    dt = jnp.abs(arr(b, nh, S)) * 0.1 + 0.01
    A = -jnp.abs(arr(nh)) - 0.1
    B = arr(b, S, N, scale=0.3)
    C = arr(b, S, N, scale=0.3)
    y_ref = ref.ssd(x, dt, A, B, C)
    y_pal = ssd_pallas(x, dt, A, B, C, bc=bc, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)
    y_xla = ops._xla_ssd(x, dt, A, B, C, bc=bc)
    np.testing.assert_allclose(np.asarray(y_xla, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_step_matches_scan():
    b, nh, S, dp, N = 2, 2, 16, 8, 16
    x = arr(b, nh, S, dp)
    dt = jnp.abs(arr(b, nh, S)) * 0.1 + 0.01
    A = -jnp.abs(arr(nh)) - 0.1
    B, C = arr(b, S, N), arr(b, S, N)
    y_ref = ref.ssd(x, dt, A, B, C)
    h = jnp.zeros((b, nh, dp, N))
    for t in range(S):
        h, y = ops.ssd_step(h, x[:, :, t], dt[:, :, t], A, B[:, t], C[:, t])
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref[:, :, t]),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W,bc", [(1, 64, 32, 16), (3, 128, 48, 64),
                                      (2, 80, 16, 16)])
def test_rglru_vs_ref(B, S, W, bc):
    a = jnp.abs(arr(B, S, W)) * 0.2
    b = arr(B, S, W)
    h_ref = ref.rglru(a, b)
    h_pal = rglru_pallas(a, b, bc=bc, interpret=True)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)
    h_xla = ops._xla_rglru(a, b)
    np.testing.assert_allclose(np.asarray(h_xla), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)


def test_rglru_ops_pads_nondivisible_seq():
    """The dispatcher pads S up to the chunk size (interpret path)."""
    import os
    B, S, W = 2, 80, 16                    # 80 % 256 != 0
    a = jnp.abs(arr(B, S, W)) * 0.2
    b = arr(B, S, W)
    h_ref = ref.rglru(a, b)
    os.environ["REPRO_PALLAS"] = "interpret"
    try:
        h = ops.rglru_scan(a, b, bc=32)
    finally:
        os.environ.pop("REPRO_PALLAS")
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-5, rtol=1e-5)


def test_rglru_step_matches_scan():
    B, S, W = 2, 12, 8
    a = jnp.abs(arr(B, S, W)) * 0.3
    b = arr(B, S, W)
    h_ref = ref.rglru(a, b)
    h = jnp.zeros((B, W))
    for t in range(S):
        h = ops.rglru_step(h, a[:, t], b[:, t])
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref[:, t]),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# weight transform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,bn,bm", [(64, 64, 32, 32), (100, 70, 32, 32),
                                       (17, 300, 8, 128)])
def test_weight_transform_dequant(n, m, bn, bm):
    w8 = jnp.asarray(R.integers(-127, 128, (n, m)), jnp.int8)
    sc = jnp.abs(arr(m)) * 0.01 + 1e-4
    o_ref = ref.weight_transform(w8, sc, jnp.float32)
    o_pal = wt_pallas(w8, sc, out_dtype=jnp.float32, bn=bn, bm=bm,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=1e-6, rtol=1e-6)


def test_weight_transform_cast():
    w = arr(50, 130)
    o = wt_pallas(w, out_dtype=jnp.bfloat16, bn=16, bm=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(o),
                                  np.asarray(w.astype(jnp.bfloat16)))


# ---------------------------------------------------------------------------
# quant matmul (fused dequant, w8a16)
# ---------------------------------------------------------------------------

from repro.kernels.quant_matmul import quant_matmul as qm_pallas  # noqa: E402


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (8, 512, 1024, 8, 128, 256),      # decode: few rows, wide weight
    (128, 256, 512, 64, 128, 128),    # prefill block
    (100, 70, 33, 32, 32, 16),        # nothing divides: padding path
    (17, 300, 5, 8, 64, 4),           # tiny N (stacked-gate leaves)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_vs_ref(m, k, n, bm, bk, bn, dtype):
    x = arr(m, k, dtype=dtype)
    w = jnp.asarray(R.integers(-127, 128, (k, n)), jnp.int8)
    sc = jnp.abs(arr(n)) * 0.02 + 1e-4
    o_ref = ref.quant_matmul(x, w, sc, dtype)
    o_pal = qm_pallas(x, w, sc, out_dtype=dtype, bm=bm, bk=bk, bn=bn,
                      interpret=True)
    assert o_pal.shape == (m, n) and o_pal.dtype == dtype
    # K is accumulated in bk-sized tiles vs the reference's single dot,
    # so f32 picks up summation-order noise past 2e-5
    t = dict(atol=1e-3, rtol=1e-3) if dtype == jnp.float32 else tol(dtype)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), **t)


def test_quant_matmul_dispatch_leading_dims():
    """ops.quant_matmul collapses leading activation dims (the model
    einsums feed (B, S, K)) and restores them on the output."""
    B, S, K, N = 2, 6, 32, 24
    x = arr(B, S, K)
    w = jnp.asarray(R.integers(-127, 128, (K, N)), jnp.int8)
    sc = jnp.abs(arr(N)) * 0.02 + 1e-4
    o = ops.quant_matmul(x, w, sc)
    assert o.shape == (B, S, N)
    o_ref = ref.quant_matmul(x.reshape(B * S, K), w, sc).reshape(B, S, N)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("eq,wshape,n_contract", [
    ("bsd,dw->bsw", (32, 48), 1),          # dense / griffin projection
    ("bshk,hkd->bsd", (4, 8, 32), 2),      # attn output fold
    ("bsd,dhk->bshk", (32, 4, 8), 1),      # qkv projection
])
def test_quant_einsum_matches_dequant_einsum(eq, wshape, n_contract):
    """quant.einsum == einsum against the dequantized weight, for every
    weight layout the model layers dispatch (scale tiles across middle
    output axes; multi-axis contractions collapse row-major)."""
    from repro import quant

    wq = jnp.asarray(R.integers(-127, 128, wshape), jnp.int8)
    sc = jnp.abs(arr(wshape[-1])) * 0.02 + 1e-4
    leaf = quant.QuantLeaf(wq, sc)
    if n_contract == 1:
        x = arr(2, 5, wshape[0])
    else:
        x = arr(2, 5, *wshape[:n_contract])
    got = quant.einsum(eq, x, leaf, jnp.float32, n_contract=n_contract)
    want = jnp.einsum(eq, x, leaf.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_quant_expert_einsum_matches_dequant():
    """MoE expert dispatch: every expert's slab shares the per-column
    scale; both the routed (per-expert x) and dense-oracle (shared x)
    forms must match the dequantized einsum."""
    from repro import quant

    E, d, f = 4, 16, 24
    wq = jnp.asarray(R.integers(-127, 128, (E, d, f)), jnp.int8)
    sc = jnp.abs(arr(f)) * 0.02 + 1e-4
    leaf = quant.QuantLeaf(wq, sc)
    x_routed = arr(2, E, 3, d)                 # (B, E, C, d)
    got = quant.expert_einsum("becd,edf->becf", x_routed, leaf,
                              jnp.float32)
    want = jnp.einsum("becd,edf->becf", x_routed,
                      leaf.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    x_shared = arr(2, 3, d)                    # (B, S, d), dense oracle
    got = quant.expert_einsum("bsd,edf->besf", x_shared, leaf,
                              jnp.float32, shared_x=True)
    want = jnp.einsum("bsd,edf->besf", x_shared,
                      leaf.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_quant_gather_rows_bit_identical():
    """Gather-then-dequant == dequant-then-gather, bit for bit (the
    embedding lookup never materializes the dequantized table)."""
    from repro import quant

    V, D = 64, 16
    wq = jnp.asarray(R.integers(-127, 128, (V, D)), jnp.int8)
    sc = jnp.abs(arr(D)) * 0.02 + 1e-4
    leaf = quant.QuantLeaf(wq, sc)
    idx = jnp.asarray(R.integers(0, V, (2, 7)), jnp.int32)
    got = quant.gather_rows(leaf, idx, jnp.bfloat16)
    want = leaf.astype(jnp.bfloat16)[idx]
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_quant_matmul_interpret_matches_ref_mode():
    """The registry's interpret path (divisor tiles, no padding) agrees
    with the ref fallback through the same dispatcher."""
    import os

    x = arr(12, 80)
    w = jnp.asarray(R.integers(-127, 128, (80, 40)), jnp.int8)
    sc = jnp.abs(arr(40)) * 0.02 + 1e-4
    os.environ["REPRO_PALLAS"] = "ref"
    try:
        o_ref = ops.quant_matmul(x, w, sc)
    finally:
        os.environ.pop("REPRO_PALLAS")
    os.environ["REPRO_PALLAS"] = "interpret"
    try:
        o_int = ops.quant_matmul(x, w, sc)
    finally:
        os.environ.pop("REPRO_PALLAS")
    np.testing.assert_allclose(np.asarray(o_int), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_autotuned_blocks_overlay():
    """set/load_autotuned overlay kernel_blocks() per profile; backend
    mismatches are skipped; clear restores the static profile."""
    from repro.configs import shapes

    base = shapes.kernel_blocks("tpu")
    art = {"autotune": {
        "quant_matmul": {"backend": "cpu",
                         "winner": {"qm_bm": 128, "qm_bk": 256,
                                    "qm_bn": 128}},
        "weight_transform": {"backend": "other",
                             "winner": {"wt_bn": 64}}}}
    try:
        applied = shapes.load_autotuned(art, backend="cpu", profile="tpu")
        assert applied == {"qm_bm": 128, "qm_bk": 256, "qm_bn": 128}
        kb = shapes.kernel_blocks("tpu")
        assert (kb.qm_bm, kb.qm_bk, kb.qm_bn) == (128, 256, 128)
        assert kb.wt_bn == base.wt_bn          # backend mismatch skipped
        assert kb.flash_bq == base.flash_bq    # untouned fields intact
    finally:
        shapes.clear_autotuned()
    kb = shapes.kernel_blocks("tpu")
    assert (kb.qm_bm, kb.qm_bk, kb.qm_bn) == \
        (base.qm_bm, base.qm_bk, base.qm_bn)
