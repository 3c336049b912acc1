"""Cells, configurations, mixes, metrics and costs are found by name, and
a new configuration, mix and metric need new files only."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench.lib import peaks, spec
from conftest import FIXTURES, ROOT, fresh_programs, tiny_benchmark


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for m in b["per_layer"] + b["end_to_end"]:
        spec.load_module("metrics", m["name"])


def test_costs_found_by_name():
    for name in ("flash_attention", "decode_attention_paged",
                 "decode_step", "prefill_step"):
        assert callable(spec.load_module("costs", name).flops)
    with pytest.raises(spec.SpecError):
        spec.load_module("costs", "no_such_kernel")


def test_missing_files_are_errors(bench_copy):
    b = tiny_benchmark()
    b["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(spec.SpecError):
        spec.load_cell("tiny.open", benchmark=b, bench=str(bench_copy))
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell", benchmark=b, bench=str(bench_copy))


def test_unknown_device_kind_raises():
    assert peaks.for_kind("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("TPU v99")
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("cpu")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "smollm-360m.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"),
         "--workload", "smollm-360m.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_added_config_mix_and_metric_run(bench_copy, cpu_peaks):
    """A new configuration, mix and per-layer metric are new files and a
    new entry: the harness finds and runs them, editing no file."""
    (bench_copy / "metrics" / "decode.tokens_per_step.py").write_text(
        "def read(run):\n"
        "    steps = run.counters.get('decode_steps', 0)\n"
        "    n = sum(len(r.times) - 1 for r in run.records if r.ok)\n"
        "    return n / steps if steps else None\n")
    b = tiny_benchmark()
    b["per_layer"].append({"name": "decode.tokens_per_step", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "DecodeScheduler",
                           "moves": "tokens_per_s",
                           "workloads": ["tiny.open"]})
    cell = spec.load_cell("tiny.open", benchmark=b, bench=str(bench_copy))
    from bench import run as run_mod
    fresh_programs()
    out = run_mod.run_cell(cell, 2**31 + 77, 2.0, 1, jax.devices()[:1],
                           cpu_peaks, kernel_mode="interpret")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["decode.tokens_per_step"]["value"] >= 1.0
    assert {"decode.occupancy", "decode.step_ms", "router.queue_ms"} <= \
        set(out["metrics"])
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def test_added_traffic_kind_runs(bench_copy, cpu_peaks):
    """A new arrival shape is a kind module and a mix naming it, both new
    files: the harness plans and drives it, editing no file."""
    import shutil
    shutil.copy(os.path.join(FIXTURES, "bursts.py"), bench_copy / "traffic")
    shutil.copy(os.path.join(FIXTURES, "tiny-bursts.json"),
                bench_copy / "traffic")
    b = tiny_benchmark()
    b["workloads"].append({"name": "tiny.bursts", "config": "tiny-decoder",
                           "traffic": "tiny-bursts", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "ttft_p75_s":
            m["workloads"].append("tiny.bursts")
    cell = spec.load_cell("tiny.bursts", benchmark=b, bench=str(bench_copy))
    assert cell.kind.__name__.endswith("bursts")
    dues = [p.due for p in cell.kind.plan(cell.traffic, 5, 2.0)]
    assert dues == [0.0] * 3 + [1.0] * 3 + [2.0] * 3
    from bench import run as run_mod
    fresh_programs()
    out = run_mod.run_cell(cell, 2**31 + 99, 2.0, 0, jax.devices()[:1],
                           cpu_peaks, kernel_mode="interpret")
    assert out["correct"] is True, out["checks"]
    # bursts at 0.5 and 1.5 s after the window opens
    assert out["attempted"] == 6 and out["failed"] == 0
    assert "ttft_p75_s" in out["metrics"]
