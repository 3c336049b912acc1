"""Quantized-resident serving (``compute_quant``): int8 leaves stay
resident as :class:`~repro.quant.QuantLeaf` (no ``weight_transform`` at
commit), forwards dispatch the fused-dequant ``quant_matmul`` kernel,
and generation stays token-identical to the dequant-at-load reference.

On the CPU these tests exercise interpret mode — the same kernel bodies
walked by the interpreter; ``tests/test_tpu_compile.py`` compiles
``quant_matmul`` and ``weight_transform`` for a TPU at real widths.
"""
import dataclasses
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.coldstart import ColdStartEngine
from repro.kernels import ops
from repro.models import transformer
from repro.models.api import get_config
from repro.quant import QuantLeaf
from repro.serving import (DecodeScheduler, GenerateSpec, Request,
                           reference_generate)
from repro.serving.engine import ServerlessPlatform
from repro.store.store import WeightStore, deploy_model

CACHE_LEN = 64
PROMPT_LEN = 8

# dense / MoE / hybrid smoke archs (f32 so token identity is meaningful)
GEN_ARCHS = ["smollm-360m", "mixtral-8x7b", "recurrentgemma-2b"]


def _f32_cfg(arch):
    return dataclasses.replace(get_config(arch, smoke=True),
                               compute_dtype=jnp.float32)


def _prompt(cfg, seed):
    r = np.random.default_rng(seed)
    return r.integers(0, cfg.vocab_size, (PROMPT_LEN,)).astype(np.int32)


def _deploy_int8(tmp_path, arch):
    cfg = _f32_cfg(arch)
    m = transformer.build(cfg)
    store = WeightStore(str(tmp_path / "store"))
    deploy_model(store, m, arch, jax.random.key(0), quant="int8")
    return cfg, m, store


def _quant_load(m, arch, store):
    eng = ColdStartEngine(m, arch, store, compute_quant=True)
    cfg_batch = {"tokens": jnp.zeros((1, PROMPT_LEN), jnp.int32)}
    return eng.load(cfg_batch).params


def _leaves(params):
    return jax.tree.leaves(
        params, is_leaf=lambda l: isinstance(l, QuantLeaf))


# ---------------------------------------------------------------------------
# residency: cold-start apply keeps QuantLeaf, bytes shrink
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", GEN_ARCHS)
def test_quant_resident_params_shrink(arch, tmp_path):
    """compute_quant apply keeps int8 + scale resident: the matmul
    weights come back as QuantLeaf and total param bytes land well
    under the dequantized load's."""
    cfg, m, store = _deploy_int8(tmp_path, arch)
    qparams = _quant_load(m, arch, store)
    qleaves = [l for l in _leaves(qparams) if isinstance(l, QuantLeaf)]
    assert qleaves, "no leaf stayed quantized"
    for l in qleaves:
        assert l.q.dtype == jnp.int8
        assert l.scale.dtype == jnp.float32
        # stacked-layer leaves carry stacked (L, last) scales
        assert l.scale.shape[-1] == l.q.shape[-1]

    fparams = ColdStartEngine(m, arch, store).load(
        {"tokens": jnp.zeros((1, PROMPT_LEN), jnp.int32)}).params
    qbytes = sum(l.nbytes for l in _leaves(qparams))
    fbytes = sum(l.nbytes for l in _leaves(fparams))
    # int8 + per-column f32 scale vs f32 leaves; norms/gates stay float
    assert qbytes < 0.6 * fbytes


def test_compute_quant_rejects_mesh(tmp_path):
    """Quantized residency is single-device: shard plans describe the
    dequantized layout, so compute_quant + mesh must fail loudly."""
    cfg, m, store = _deploy_int8(tmp_path, "smollm-360m")
    with pytest.raises(ValueError, match="single"):
        ColdStartEngine(m, "smollm-360m", store, compute_quant=True,
                        mesh=types.SimpleNamespace(size=2))


def test_quantleaf_astype_matches_weight_transform():
    """The transparent fallback (QuantLeaf.astype) is bit-identical to
    the registry's dequant — untouched call sites lose nothing."""
    from repro.kernels import ref

    r = np.random.default_rng(3)
    q = jnp.asarray(r.integers(-127, 128, (48, 32)), jnp.int8)
    sc = jnp.asarray(np.abs(r.standard_normal(32)).astype(np.float32)
                     + 1e-3)
    leaf = QuantLeaf(q, sc)
    want = ref.weight_transform(q, sc, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(leaf.astype(jnp.bfloat16), np.float32),
        np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# generation identity: DecodeScheduler under compute_quant == reference
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", GEN_ARCHS)
def test_quant_generation_token_identical(arch, tmp_path, monkeypatch):
    """Quantized-resident generation through the continuous-batching
    scheduler reproduces the dequant-at-load reference token-for-token,
    under the resolved kernel mode (interpret by default, pallas on the
    TPU CI leg) — and the run actually dispatched quant_matmul."""
    import os

    mode = os.environ.get("REPRO_PALLAS")
    if mode != "pallas":
        mode = "interpret"
    monkeypatch.setenv("REPRO_PALLAS", mode)

    cfg, m, store = _deploy_int8(tmp_path, arch)
    qparams = _quant_load(m, arch, store)
    fparams = ColdStartEngine(m, arch, store).load(
        {"tokens": jnp.zeros((1, PROMPT_LEN), jnp.int32)}).params

    before = ops.registry.dispatch_snapshot()
    sched = DecodeScheduler(m, qparams, n_slots=2, cache_len=CACHE_LEN)
    spec = GenerateSpec(prompt=_prompt(cfg, 5), n_new=4)
    got = sched.generate(spec).tokens
    want = reference_generate(m, fparams, spec.prompt, n_new=4,
                              cache_len=CACHE_LEN)
    assert got == want
    after = ops.registry.dispatch_snapshot()
    assert after.get(("quant_matmul", mode), 0) > \
        before.get(("quant_matmul", mode), 0)


@pytest.mark.slow
def test_quant_generation_token_identical_ref_mode(tmp_path, monkeypatch):
    """Same identity through the pure-jnp ref dispatch (the CPU hot
    path serving actually takes)."""
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    cfg, m, store = _deploy_int8(tmp_path, "smollm-360m")
    qparams = _quant_load(m, "smollm-360m", store)
    fparams = ColdStartEngine(m, "smollm-360m", store).load(
        {"tokens": jnp.zeros((1, PROMPT_LEN), jnp.int32)}).params
    sched = DecodeScheduler(m, qparams, n_slots=2, cache_len=CACHE_LEN)
    spec = GenerateSpec(prompt=_prompt(cfg, 7), n_new=4)
    assert sched.generate(spec).tokens == reference_generate(
        m, fparams, spec.prompt, n_new=4, cache_len=CACHE_LEN)


# ---------------------------------------------------------------------------
# platform end-to-end: --compute-quant residency under a fixed budget
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_quant_platform_generation_and_double_residency(tmp_path):
    """End-to-end through the platform Router: quantized generation
    matches the reference, and a cache budget sized *between* two int8
    residencies and two f32 residencies keeps BOTH models warm — the
    halved footprint is what buys the second resident model."""
    arch = "smollm-360m"
    cfg, m, store = _deploy_int8(tmp_path, arch)
    # second int8 deploy of the same arch under another name
    deploy_model(store, m, f"{arch}-b", jax.random.key(1), quant="int8")

    # size the budget from the actual quant/f32 residencies
    qbytes = sum(l.nbytes for l in _leaves(_quant_load(m, arch, store)))
    fbytes = sum(l.nbytes for l in _leaves(
        ColdStartEngine(m, arch, store).load(
            {"tokens": jnp.zeros((1, PROMPT_LEN), jnp.int32)}).params))
    budget = int(2.2 * qbytes)
    assert 2 * qbytes <= budget < 2 * fbytes, \
        "smoke arch residencies no longer separate the budget"

    example = {"tokens": jnp.asarray(_prompt(cfg, 99)[None])}
    platform = ServerlessPlatform(
        store, {arch: lambda: (m, example),
                f"{arch}-b": lambda: (m, example)},
        strategy="cicada", keep_alive_s=1e9, max_instances=1,
        gen_slots=2, gen_cache_len=CACHE_LEN,
        cache_budget_bytes=budget, compute_quant=True)
    spec = GenerateSpec(prompt=_prompt(cfg, 11), n_new=4)
    with platform.router(workers=2) as router:
        got_a = router.submit(
            Request(req_id=0, model=arch, gen=spec)).result().tokens
        got_b = router.submit(
            Request(req_id=1, model=f"{arch}-b", gen=spec)).result().tokens
    fparams = ColdStartEngine(m, arch, store).load(
        {"tokens": jnp.zeros((1, PROMPT_LEN), jnp.int32)}).params
    assert list(got_a) == list(reference_generate(
        m, fparams, spec.prompt, n_new=4, cache_len=CACHE_LEN))
    assert len(got_b) == 4

    stats = platform.cache_stats()
    assert stats.evictions == 0, \
        "two int8 models must co-reside under the budget"
    assert stats.bytes_cached <= budget
    for name in (arch, f"{arch}-b"):
        inst = platform.pools[name]._instances[0]
        assert any(isinstance(l, QuantLeaf) for l in _leaves(inst.params))


# ---------------------------------------------------------------------------
# autotuned block overlay plumbing (shapes <-> kernels_micro artifact)
# ---------------------------------------------------------------------------

def test_load_autotuned_roundtrip():
    from repro.configs import shapes

    art = {"autotune": {"quant_matmul": {
        "backend": "cpu", "winner": {"qm_bm": 128, "qm_bk": 512,
                                     "qm_bn": 128}}}}
    try:
        assert shapes.load_autotuned(art, backend="cpu",
                                     profile="tpu") != {}
        kb = shapes.kernel_blocks("tpu")
        assert (kb.qm_bm, kb.qm_bk, kb.qm_bn) == (128, 512, 128)
        # other-backend artifacts must not leak in
        assert shapes.load_autotuned(art, backend="tpu",
                                     profile="tpu") == {}
    finally:
        shapes.clear_autotuned()
