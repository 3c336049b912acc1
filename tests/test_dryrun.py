"""Dry-run machinery self-test (subprocess: it forces 512 host devices).

Covers one cell per step kind (train / prefill / decode / long-decode)
at reduced config on both production mesh shapes, plus the skip logic.
Full-size cells are exercised by ``python -m repro.launch.dryrun --all``
(see EXPERIMENTS.md §Dry-run); they are too slow for unit CI.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_dryrun(*args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.slow
def test_smoke_train_both_meshes(tmp_path):
    out = tmp_path / "r.json"
    r = run_dryrun("--arch", "yi-9b", "--shape", "train_4k", "--smoke",
                   "--both-meshes", "--out", str(out))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    recs = json.loads(out.read_text())
    assert [x["mesh"] for x in recs] == ["16x16", "2x16x16"]
    assert all(x["status"] == "ok" for x in recs)
    assert recs[0]["devices"] == 256 and recs[1]["devices"] == 512
    # single-pod record carries roofline costs
    assert recs[0]["cost_per_device"]["flops"] > 0
    assert recs[0]["cost_per_device"]["collectives"]["total"] > 0
    assert "micro_batches" in recs[0]


@pytest.mark.slow
def test_smoke_decode_and_skip(tmp_path):
    out = tmp_path / "r.json"
    r = run_dryrun("--arch", "mamba2-780m", "--shape", "long_500k",
                   "--smoke", "--out", str(out))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(out.read_text())[0]
    assert rec["status"] == "ok"            # SSM runs long-context decode

    r2 = run_dryrun("--arch", "yi-9b", "--shape", "long_500k",
                    "--smoke", "--out", str(out))
    rec2 = json.loads(out.read_text())[0]
    assert rec2["status"] == "skip"
    assert "full attention" in rec2["skip_reason"]

    r3 = run_dryrun("--arch", "hubert-xlarge", "--shape", "decode_32k",
                    "--smoke", "--out", str(out))
    rec3 = json.loads(out.read_text())[0]
    assert rec3["status"] == "skip"
    assert "encoder" in rec3["skip_reason"]


@pytest.mark.slow
def test_smoke_moe_prefill(tmp_path):
    out = tmp_path / "r.json"
    r = run_dryrun("--arch", "mixtral-8x7b", "--shape", "prefill_32k",
                   "--smoke", "--out", str(out))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.loads(out.read_text())[0]
    assert rec["status"] == "ok"
    assert rec["memory"]["live_bytes_per_device"] > 0
