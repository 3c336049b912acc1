"""``correct`` against its limit: the control, and the faults a cell can
have, all planted under a run that skips only the look for a chip.

The tiny configuration's limit, 0.1 logit RMS, lies between its
readings on the CPU: sound runs gave at most 0.036 on four seeds in each
tiny cell, the float8 control at least 0.52."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness, spec, weights
from bench.lib.compile_stats import CompileStats
from conftest import FIXTURES, fresh_programs, tiny_benchmark


def test_control_fails_and_the_program_passes(bench_copy):
    cell = spec.load_cell("tiny.open", benchmark=tiny_benchmark(),
                          bench=str(bench_copy))
    limit = cell.config["check"]["max_gap"]
    fresh_programs()
    prog = harness.Cell(cell, 2**31 + 3, 2.0, stats=CompileStats(),
                        log=lambda m: None)
    prog.warm_up()
    run = prog.drive()
    prog.close()
    got = harness.correctness(cell, run, control=True)
    assert got["tokens"] >= 40
    assert got["max_gap"] <= limit < got["control_max_gap"], got


def alter_token(monkeypatch):
    """Every token comes out one id off where it is sampled: the
    pipeline's first token, prefill's and the decode step's."""
    from repro.serving import decode
    orig = decode.sample_tokens
    monkeypatch.setattr(decode, "sample_tokens",
                        lambda lg, *a: (orig(lg, *a) + 1) % lg.shape[-1])
    return {}


def stale_state(monkeypatch):
    """The decode step returns the KV pages it was given, unchanged."""
    from repro.models import transformer
    orig = transformer.LM.decode_step_paged

    def step(self, params, cache, pools, *a, **kw):
        logits, cache, _ = orig(self, params, cache, pools, *a, **kw)
        return logits, cache, pools
    monkeypatch.setattr(transformer.LM, "decode_step_paged", step)
    return {}


def control(monkeypatch):
    """The control's choices judged in the served tokens' place, by the
    run's own comparison and limit."""
    return {"control": True}


@pytest.mark.parametrize("workload,fault", [
    ("tiny.open", alter_token), ("tiny.open", stale_state),
    ("tiny.cold", alter_token), ("tiny.cold", stale_state),
    ("tiny.open", control), ("tiny.cold", control)])
def test_fault_makes_the_run_incorrect(bench_copy, cpu_peaks, monkeypatch,
                                       workload, fault):
    from bench import run as run_mod
    cell = spec.load_cell(workload, benchmark=tiny_benchmark(),
                          bench=str(bench_copy))
    kwargs = fault(monkeypatch)
    fresh_programs()
    try:
        out = run_mod.run_cell(cell, 2**31 + 11, 2.0, 0, jax.devices()[:1],
                               cpu_peaks, kernel_mode="interpret",
                               log=lambda m: None, **kwargs)
    finally:
        monkeypatch.undo()
        fresh_programs()
    assert out["correct"] is False
    assert out["checks"]["max_gap"]["value"] > \
        out["checks"]["max_gap"]["limit"]


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_reference_matches_the_program_forward(tied):
    """The reference and the program's own float32 forward agree on the
    benchmark's weights, with a tied head and with one of its own."""
    from repro.models import transformer
    with open(os.path.join(FIXTURES, "tiny-decoder.json")) as f:
        cfg = json.load(f)
    cfg["tie_word_embeddings"] = tied
    cfg["serving"]["compute_dtype"] = "float32"
    units = weights.make(cfg, 2**33 + 5)
    assert ("head" in units["final"]) is not tied
    model = transformer.build(harness._arch(cfg))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 24)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(model.assemble(units),
                               {"tokens": jnp.asarray(tokens)[None]})
    ref = spec.load_module("reference", cfg["reference"])
    want = np.asarray(ref.logits(cfg, units, tokens))
    got = np.asarray(got[0], np.float32)
    rms = np.sqrt(np.mean(want ** 2))
    assert np.max(np.abs(got - want)) < 1e-3 * rms
