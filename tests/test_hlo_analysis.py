"""HLO collective parser + cost composition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import (collective_bytes, combine_linear,
                                       scale_cost, shape_bytes)


def test_shape_bytes():
    assert shape_bytes("f32[128,1024]{1,0}") == 128 * 1024 * 4
    assert shape_bytes("bf16[2,4,8]") == 64 * 2
    assert shape_bytes("(f32[16], bf16[16])") == 64 + 32
    assert shape_bytes("pred[]") == 1           # scalar: empty dims -> 1 elt
    assert shape_bytes("token[]") == 0          # unknown dtypes ignored


def test_collective_parse_basic():
    hlo = """
  %ag = f32[256,1024]{1,0} all-gather(f32[16,1024]{1,0} %x), dimensions={0}
  %ar = bf16[128,128]{1,0} all-reduce(bf16[128,128]{1,0} %y), to_apply=%add
  %rs.1 = f32[8,64]{1,0} reduce-scatter(f32[64,64] %z), dimensions={0}
  %cp = f32[32]{0} collective-permute(f32[32]{0} %w), source_target_pairs={{0,1}}
  %done = f32[256,1024]{1,0} all-gather-done(f32[256,1024] %ag)
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 256 * 1024 * 4
    assert got["all-reduce"] == 128 * 128 * 2
    assert got["reduce-scatter"] == 8 * 64 * 4
    assert got["collective-permute"] == 32 * 4
    assert got["_counts"]["all-gather"] == 1     # -done not double counted
    assert got["total"] == sum(got[k] for k in
                               ("all-gather", "all-reduce",
                                "reduce-scatter", "collective-permute"))


def test_collective_parse_async_start():
    hlo = "%a = (f32[16]{0}, f32[64]{0}) all-gather-start(f32[16] %x)\n"
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 4 + 64 * 4


def test_collective_parse_real_lowering():
    """Parse actual XLA output of a psum under 1-device SPMD (no
    collectives expected) and of a manual HLO check above."""
    c = jax.jit(lambda x: x * 2).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    got = collective_bytes(c.as_text())
    assert got["total"] == 0


def test_combine_linear():
    c1 = {"flops": 10.0, "collectives": {"all-reduce": 4, "total": 4}}
    c2 = {"flops": 16.0, "collectives": {"all-reduce": 6, "total": 6}}
    out = combine_linear(c1, c2, n_units=5)
    assert out["flops"] == 10 + 4 * 6.0
    assert out["collectives"]["all-reduce"] == 4 + 4 * 2
    # degenerate: n_units == 1 -> exactly c1
    out1 = combine_linear(c1, c2, n_units=1)
    assert out1["flops"] == 10.0


def test_combine_linear_clamps_negative_delta():
    c1 = {"flops": 10.0}
    c2 = {"flops": 9.0}      # compiler noise
    out = combine_linear(c1, c2, 10)
    assert out["flops"] == 10.0


def test_scale_cost():
    c = {"flops": 2.0, "collectives": {"total": 3}}
    out = scale_cost(c, 8)
    assert out == {"flops": 16.0, "collectives": {"total": 24}}


def test_unrolled_scan_cost_exactness():
    """The machinery's reason to exist: scan undercounts, unroll doesn't."""
    d = 64

    def fwd(x, ws, unroll):
        if unroll:
            for i in range(4):
                x = jnp.tanh(x @ ws[i])
            return x
        return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None),
                            x, ws)[0]

    xs = jax.ShapeDtypeStruct((8, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, d, d), jnp.float32)
    analytic = 2 * 8 * d * d * 4
    def cost(compiled):
        return compiled.cost_analysis()

    f_scan = jax.jit(lambda x, w: fwd(x, w, False)).lower(xs, ws).compile()
    f_unrl = jax.jit(lambda x, w: fwd(x, w, True)).lower(xs, ws).compile()
    assert cost(f_scan)["flops"] < analytic * 0.5
    assert cost(f_unrl)["flops"] == pytest.approx(analytic, rel=0.01)
