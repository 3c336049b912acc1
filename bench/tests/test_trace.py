"""The reduction from a device trace to metrics, on a recorded trace.

``fixtures/trace_smollm_cold.json`` is a piece of a TPU v5e trace of
the smollm-360m.cold cell: one scheduler prefill of the 512-token prompt
(32 flash-attention calls, one per layer) and one batched decode step
(``jit_step``, 32 paged decode-attention calls), with the benchmark's
clock mark at 0.  Op names are cut to their first 160 characters."""
import os
import types

import pytest

from bench.lib import harness, peaks, spec, trace
from conftest import FIXTURES

V5E = peaks.for_kind("TPU v5 lite")


@pytest.fixture(scope="module")
def view():
    return trace.View(trace.load_json(
        os.path.join(FIXTURES, "trace_smollm_cold.json")), mark_s=0.0)


def run_with(view, records=()):
    r = harness.Run(cell=types.SimpleNamespace(
                        config=spec.load_config("smollm-360m")),
                    seed=0, seconds=1.0, w0=0.0, w1=1.0,
                    records=list(records), counters={}, trace=view,
                    trace_window=(0.0, 0.36), peaks=V5E)
    return r


def test_op_names():
    n = ("%flash_attention.1 = bf16[1,15,512,64]{3,2,1,0:T(8,128)} "
         "custom-call(bf16[1,15,512,64]")
    assert trace.op_kind(n) == "flash_attention"
    assert trace.out_dims(n) == [1, 15, 512, 64]
    assert trace.op_kind("%while.6 = (s32[], bf16[64]) while(") == "while"
    assert trace.op_kind("%copy-done.3 = f32[2] copy-done(") == "copy-done"


def test_kernels_and_programs(view):
    flash = view.kernel("flash_attention")
    paged = view.kernel("decode_attention_paged")
    assert len(flash) == 32 and len(paged) == 32
    assert {tuple(trace.out_dims(e.name)) for e in flash} == \
        {(1, 15, 512, 64)}
    steps = view.module("jit_step")
    assert len(steps) == 1
    assert steps[0].dur_ns == pytest.approx(280.31e6, rel=1e-4)
    assert len(view.within(steps[0], paged)) == 32
    assert sum(e.dur_ns for e in flash) == pytest.approx(1.891e6, rel=1e-3)


def test_busy_is_a_union_and_top_ops_skip_loops(view):
    busy = view.busy_s(0.0, 0.36)
    assert busy == pytest.approx(0.3293, rel=1e-3)
    assert busy < 0.36
    top = view.top_ops(0.0, 0.36)
    names = [k for k, _ in top]
    assert names[0] == "decode_attention_paged" and "while" not in names
    assert len(top) <= 10
    assert sum(v for _, v in top) <= busy + 1e-9


def test_idle_gaps_label_what_the_host_did(view):
    gaps = view.idle_gaps(0.0, 0.36)
    assert gaps and all(v > 0 for _, v in gaps)
    assert sum(v for _, v in gaps) <= 0.36 - view.busy_s(0.0, 0.36) + 1e-9


def test_flash_roofline_and_prefill_mfu(view):
    run = run_with(view)
    # 32 calls at n = 512, each bound by 2,621,440 bytes at 819 GB/s
    want = 32 * 2_621_440 / 819e9 / 1.891099e-3
    got = spec.load_module("metrics", "flash_attention_roofline").read(run)
    assert got == pytest.approx(100 * want, rel=1e-6)
    # one 512-token prefill: 338,354,503,680 FLOPs in 10.282342 ms
    mfu = spec.load_module("metrics", "prefill.mfu").read(run)
    assert mfu == pytest.approx(100 * 338_354_503_680 / (10.282342e-3
                                                         * 197e12), rel=1e-6)
    assert 0 < got < 100 and 0 < mfu < 100


def test_decode_roofline_and_mfu_scale_host_steps(view):
    """One host step with one live row at context 520 (the cold
    request's first decode step after a 512-token prompt)."""
    rec = harness.Record(index=0, n_prompt=519, n_new=2)
    rec.times, rec.tokens, rec.ok = [0.01, 0.2], [1, 2], True
    run = run_with(view, [rec])
    assert run.decode_steps(0.0, 0.36) == [[520]]
    kern = spec.load_module("metrics", "decode_attention_paged_roofline")
    per_call = 2 * 2 * 5 * 64 * 520 + 2 * 2 * 15 * 64
    assert kern.read(run) == pytest.approx(
        100 * 32 * per_call / 819e9 / 173.396617e-3, rel=1e-6)
    mfu = spec.load_module("metrics", "decode.mfu").read(run)
    flops = spec.load_module("costs", "decode_step").flops(
        run.cfg, [520])
    assert mfu == pytest.approx(100 * flops / (280.30826e-3 * 197e12),
                                rel=1e-5)


def test_readers_find_nothing_without_a_trace():
    run = run_with(None)
    for name in ("flash_attention_roofline", "prefill.mfu", "decode.mfu",
                 "decode_attention_paged_roofline", "device.idle.warm"):
        assert spec.load_module("metrics", name).read(run) is None
