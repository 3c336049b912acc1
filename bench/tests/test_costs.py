"""FLOP and byte counts of the kernels and steps, against counts worked
out by hand at smollm-360m widths (d 960, 15/5 heads of 64, d_ff 2560,
vocab 49152, 32 layers)."""
import pytest

from bench.lib import spec

CFG = spec.load_config("smollm-360m")
# q 960*15*64 + k, v 2*960*5*64 + o 15*64*960 + SwiGLU 3*960*2560
LAYER = 921_600 + 614_400 + 921_600 + 7_372_800


def cost(name):
    return spec.load_module("costs", name)


def test_layer_weights():
    assert cost("decode_step").layer_params(CFG) == LAYER == 9_830_400


def test_flash_attention():
    c = cost("flash_attention")
    # 4 * 15 * 64 * 512 * 513 / 2
    assert c.flops(CFG, 512) == 504_299_520
    # q and out (15 heads), k and v (5 heads): 512 * 64 * 40 * 2 bytes
    assert c.nbytes(CFG, 512) == 2_621_440


def test_decode_attention_paged():
    c = cost("decode_attention_paged")
    assert c.flops(CFG, [100, 200]) == 4 * 15 * 64 * 300 == 1_152_000
    # k and v over 300 positions of 5 heads, q and out of 2 rows
    assert c.nbytes(CFG, [100, 200]) == 384_000 + 7_680
    assert c.flops(CFG, []) == 0


def test_decode_step():
    per_row = 2 * (32 * LAYER + 960 * 49152)
    assert per_row == 723_517_440
    attn = 4 * 15 * 64 * 32 * 300
    assert cost("decode_step").flops(CFG, [100, 200]) == \
        2 * per_row + attn == 1_483_898_880


def test_prefill_step():
    want = 2 * 512 * 32 * LAYER + 4 * 15 * 64 * 32 * 512 * 513 // 2 \
        + 2 * 960 * 49152
    assert want == 338_354_503_680
    assert cost("prefill_step").flops(CFG, 512) == pytest.approx(want,
                                                                 rel=1e-12)
