"""The main-path Pallas kernels compile for a TPU v5e at smollm-360m
widths (15 query heads, 5 KV heads, head dim 64, d 960, d_ff 2560).

Nothing runs: each kernel is compiled, from shapes only, for one chip of
a described ``v5e:2x2`` topology, which catches what interpret mode
cannot (tile alignment, VMEM limits, unsupported primitives).  The
topology is described inside a fixture, so only the worker that runs
this file loads the TPU compiler; where it cannot be described, every
test here skips.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.shapes import kernel_blocks
from repro.kernels import ops
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.weight_transform import weight_transform

H, K, DH, D, FF = 15, 5, 64, 960, 2560
BF, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep it out of the way while these compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """Replicated placement on a (1, 4) mesh of the described chips, as
    a tensor-parallel instance holds its attention operands."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    return NamedSharding(mesh, PartitionSpec())


def _compile(fn, sharding, shapes, **kw):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding)
            for s in shapes]
    hlo = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in hlo


KB = kernel_blocks("tpu")


@pytest.mark.parametrize("S,T", [
    (256, 256),      # tile multiple
    (300, 300),      # padded to whole tiles
    (12, 300),       # prefix-cache continuation: 12 new tokens after 288
    (256, 1216),     # chunked prefill against a 960-token prefix
])
def test_flash_attention_compiles(one_chip, S, T):
    _compile(flash_attention, one_chip,
             [((1, H, S, DH), BF), ((1, K, T, DH), BF), ((1, K, T, DH), BF)],
             causal=True, window=0, bq=KB.flash_bq, bk=KB.flash_bk)


@pytest.mark.parametrize("B,S_max", [(4, 512), (1, 300), (8, 600)])
def test_decode_attention_compiles(one_chip, B, S_max):
    _compile(decode_attention, one_chip,
             [((B, H, DH), BF), ((B, K, S_max, DH), BF),
              ((B, K, S_max, DH), BF), ((B,), I32)],
             window=0, bs=KB.decode_bs)


@pytest.mark.parametrize("B,pages,NP", [
    (4, 129, 32),
    (64, 2289, 160),   # 64 slots of 2560 positions, a 1.5 GB page budget
])
def test_decode_attention_paged_compiles(one_chip, B, pages, NP):
    pt = 16
    _compile(decode_attention_paged, one_chip,
             [((B, H, DH), BF), ((2, pages, K, pt, 2 * DH), BF),
              ((B, NP), I32), ((B,), I32), ((), I32)],
             window=0, bs=KB.decode_bs)


@pytest.mark.parametrize("dequant", [True, False])
def test_weight_transform_compiles(one_chip, dequant):
    shapes = [((D, FF), I8), ((FF,), F32)] if dequant else \
        [((D, FF), F32), None]
    _compile(weight_transform, one_chip, shapes,
             out_dtype=F32 if dequant else BF, bn=KB.wt_bn, bm=KB.wt_bm)


@pytest.mark.parametrize("M", [300, 4])
def test_quant_matmul_compiles(one_chip, M):
    _compile(quant_matmul, one_chip,
             [((M, D), BF), ((D, FF), I8), ((FF,), F32)],
             out_dtype=BF, bm=KB.qm_bm, bk=KB.qm_bk, bn=KB.qm_bn)


@pytest.mark.parametrize("op", ["flash_attention", "decode_attention"])
def test_kernel_on_a_four_chip_mesh_compiles(four_chips, monkeypatch, op):
    """Mosaic refuses to partition a kernel: operands on a multi-chip
    mesh go through the registry's shard_map, replicated per chip."""
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    if op == "flash_attention":
        fn = jax.jit(lambda q, k, v: ops.flash_attention_kvmajor(
            q, k, v, causal=True, window=0))
        shapes = [((1, 300, H, DH), BF), ((1, K, 300, DH), BF),
                  ((1, K, 300, DH), BF)]
    else:
        fn = jax.jit(lambda q, k, v, p: ops.decode_attention(q, k, v, p))
        shapes = [((4, H, DH), BF), ((4, K, 512, DH), BF),
                  ((4, K, 512, DH), BF), ((4,), I32)]
    _compile(fn, four_chips, shapes)


YI_H, YI_K, YI_DH = 32, 4, 128     # yi-9b: 8 query heads per KV head


@pytest.mark.parametrize("kernel", ["flash_attention",
                                    "decode_attention_paged"])
def test_yi_widths_compile(one_chip, kernel):
    """yi-9b-l8.batch's shapes: a 2048-token prompt's flash attention,
    and the paged decode of 64 slots over a 1.2 GB pool of 16-token
    pages (head size 128, lane-aligned, unlike smollm's 64)."""
    if kernel == "flash_attention":
        _compile(flash_attention, one_chip,
                 [((1, YI_H, 2048, YI_DH), BF), ((1, YI_K, 2048, YI_DH), BF),
                  ((1, YI_K, 2048, YI_DH), BF)],
                 causal=True, window=0, bq=KB.flash_bq, bk=KB.flash_bk)
    else:
        pages, pt = 4578, 16
        _compile(decode_attention_paged, one_chip,
                 [((64, YI_H, YI_DH), BF),
                  ((2, pages, YI_K, pt, 2 * YI_DH), BF), ((64, 160), I32),
                  ((64,), I32), ((), I32)],
                 window=0, bs=KB.decode_bs)


# ops that would copy, slice, relayout or scatter a whole page pool
POOL_OPS = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice",
            "scatter", "fusion")


@pytest.mark.parametrize("program,arch,pages", [
    ("step", "smollm-360m", 2289),   # the chat cell's pool, head size 64
    ("step", "yi-9b", 4578),         # the yi batch cell's, head size 128
    ("pack", "smollm-360m", 2289),
])
def test_paged_programs_keep_the_pool_in_place(one_chip, monkeypatch,
                                               program, arch, pages):
    """The decode step (64 rows, 160-page tables) and the admission pack
    (a 2048-token prompt) of a two-layer model at published widths, as
    the scheduler jits them: no op of either copies, slices, relayouts
    or scatters an array with the pool's page count as a dimension, and
    the donated pool is aliased to the output, so each writes its rows
    or pages into the pool where it lies."""
    from repro.models import transformer
    from repro.models.api import get_config
    from repro.serving import decode
    monkeypatch.setenv("REPRO_PALLAS", "pallas")
    m = transformer.build(dataclasses.replace(get_config(arch), n_layers=2))
    B, NP, pt = 64, 160, 16

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), jax.eval_shape(tree))

    def arg(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = place(lambda: m.init_kv_pages(pages, pt))
    fp = ops.registry.fingerprint()
    if program == "step":
        lowered = decode._paged_step_fn(m, fp).lower(
            place(lambda: m.init(jax.random.key(0))),
            place(lambda: m.init_cache_paged(B, 2560)), pools,
            arg((B, NP)), arg((B, 1)), arg((B,)), arg((B,), jnp.uint32),
            arg((B,), F32))
    else:
        lowered = decode._pack_fn(m, fp).lower(
            pools, place(lambda: m.init_request_cache(2048, 2560)),
            arg((2048 // pt,)), 0)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    found = []
    for line in hlo.splitlines():
        got = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if (got and got.group(2) in POOL_OPS
                and re.search(rf"[\[,]{pages}[,\]]", got.group(1))):
            found.append(line.strip()[:160])
    assert not found, "\n".join(found)
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pools))
    assert "input_output_alias={" in hlo
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
