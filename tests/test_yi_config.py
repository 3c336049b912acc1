"""Yi-9B's block against the benchmark's plain reference, on the CPU.

A small configuration with Yi's proportions: an untied head, 8 query
heads per KV head (H 8, K 1), head size 128 (hidden size H x 128, as
Yi's 4096 is 32 x 128), Yi's ratio of d_ff to hidden size, 2 layers,
vocab 512.  The benchmark's own files run it: ``bench/lib/weights.py``
makes the weights, ``bench/reference/dense_decoder.py`` is the
reference, and ``bench/run.py``'s ``run_cell`` serves a closed loop
through ``Router.submit`` on a copy of the benchmark with this
configuration and mix added as new files.
"""
import copy
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench.lib import harness, peaks, spec, weights  # noqa: E402

YI = os.path.join(ROOT, "bench", "configs", "yi-9b-l8.json")
CELL = "yi-small.batch"


def small_yi():
    """yi-9b-l8.json at Yi's proportions and a CPU's size."""
    with open(YI) as f:
        cfg = json.load(f)
    assert cfg["num_attention_heads"] // cfg["num_key_value_heads"] == 8
    cfg.update(name="yi-small", num_hidden_layers=2, num_attention_heads=8,
               num_key_value_heads=1, head_dim=128, hidden_size=1024,
               intermediate_size=2752, vocab_size=512, reduced=[])
    cfg["serving"].update(gen_slots=4, gen_cache_len=128,
                          kv_budget_bytes=None)
    # between its CPU readings on four seeds: served tokens 0.0 logit
    # RMS (each the reference's best), the float8 control 0.55-0.92
    cfg["check"] = {"max_gap": 0.1, "min_tokens": 40, "max_requests": 4}
    return cfg


MIX = {"kind": "closed_loop", "clients": 3, "cold": False, "lead_in_s": 0.5,
       "prompt": {"median": 32, "sigma": 0.8, "min": 16, "max": 80,
                  "menu": [24, 72], "round": "up"},
       "output": {"median": 8, "sigma": 0.8, "min": 4, "max": 16,
                  "menu": [6, 12], "round": "nearest"},
       "temperature": 0.0, "trace_start_s": 0.2, "trace_s": 1.0}


def test_reference_matches_the_program_forward():
    """The program's float32 forward and the reference agree on the
    benchmark's weights at Yi's proportions.  Both compute in float32
    at the highest matmul precision; they differ only in the order of
    sums and in how rotary embedding and the grouped heads are laid
    out, about 1e-6 of the logits' RMS, so 1e-3 of it leaves room for
    that and none for a wrong head mapping or a missing term."""
    from repro.models import transformer
    cfg = small_yi()
    cfg["serving"]["compute_dtype"] = "float32"
    units = weights.make(cfg, 2**33 + 7)
    assert "head" in units["final"]
    model = transformer.build(harness._arch(cfg))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(model.assemble(units),
                               {"tokens": jnp.asarray(tokens)[None]})
    ref = spec.load_module("reference", cfg["reference"])
    want = np.asarray(ref.logits(cfg, units, tokens))
    got = np.asarray(got[0], np.float32)
    rms = np.sqrt(np.mean(want ** 2))
    assert np.max(np.abs(got - want)) < 1e-3 * rms


@pytest.fixture
def yi_bench(tmp_path):
    """A copy of bench/ with the small configuration and its mix added
    as new files, and BENCHMARK.json with one cell for them: the
    metrics that list yi-9b-l8.batch list it."""
    dst = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "configs" / "yi-small.json").write_text(json.dumps(small_yi()))
    (dst / "traffic" / "yi-small-batch.json").write_text(json.dumps(MIX))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"] = [{"name": CELL, "config": "yi-small",
                       "traffic": "yi-small-batch", "chips": 1,
                       "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "yi-9b-l8.batch" in m["workloads"] \
                else []
    return dst, b


@pytest.mark.parametrize("control", [False, True],
                         ids=["served", "control"])
def test_runs_through_the_benchmark(yi_bench, monkeypatch, control):
    """Served through the Router in interpret mode, the configuration
    comes out correct with no failed request; the float8 control,
    judged by the same limit, does not."""
    from bench import run as run_mod
    from repro.serving import decode
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    bench_dir, b = yi_bench
    cell = spec.load_cell(CELL, benchmark=copy.deepcopy(b),
                          bench=str(bench_dir))
    assert cell.config["num_attention_heads"] == 8
    for f in (decode._prefill_fn, decode._paged_step_fn, decode._pack_fn):
        f.cache_clear()
    cpu = peaks.Peaks(bf16_flops=1e12, int8_ops=1e12,
                      hbm_bytes_per_s=1e11, hbm_bytes=1e9)
    out = run_mod.run_cell(cell, 2**31 + 41, 2.0, 0, jax.devices()[:1], cpu,
                           kernel_mode="interpret", log=lambda m: None,
                           control=control)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["kernels_not_interpret"]["value"] == 0
    assert out["correct"] is not control, out["checks"]
    assert {"itl_p95_ms", "setup_s"} <= set(out["metrics"])
