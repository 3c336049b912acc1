"""CPU tests of the benchmark's harness: ``pytest bench/tests``.

They drive everything a run does after its look for a chip, at a tiny
size on the CPU, with the Pallas kernels in interpret mode."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_PALLAS"] = "interpret"

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TINY_CELLS = {"smollm-360m.chat": "tiny.open",
              "smollm-360m.cold": "tiny.cold"}


def tiny_benchmark():
    """BENCHMARK.json with its cells replaced by the tiny fixture cells,
    and each metric's cell list mapped onto them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"] = [
        {"name": "tiny.open", "config": "tiny-decoder",
         "traffic": "tiny-open", "chips": 1, "why": "test"},
        {"name": "tiny.cold", "config": "tiny-decoder",
         "traffic": "tiny-cold", "chips": 1, "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({TINY_CELLS.get(w, w)
                                     for w in m["workloads"]})
    return b


def fresh_programs():
    """Drop the program's cached jitted steps, so that a planted fault
    (or its removal) is traced anew, and the kernels' dispatch is
    counted again."""
    from repro.serving import decode
    for f in (decode._prefill_fn, decode._step_fn, decode._paged_step_fn,
              decode._prefill_cont_fn, decode._pack_fn, decode._join_fn,
              decode._gather_fn):
        f.cache_clear()


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of bench/ with the tiny fixture configuration and mixes
    added as new files, as a later change would add them."""
    dst = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(FIXTURES, "tiny-decoder.json"),
                dst / "configs")
    for t in ("tiny-open", "tiny-cold"):
        shutil.copy(os.path.join(FIXTURES, f"{t}.json"), dst / "traffic")
    return dst


@pytest.fixture(scope="session")
def cpu_peaks():
    from bench.lib import peaks
    return peaks.Peaks(bf16_flops=1e12, int8_ops=1e12,
                       hbm_bytes_per_s=1e11, hbm_bytes=1e9)
