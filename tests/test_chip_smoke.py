"""chip_smoke.py off the chip: its float32 reference, its refusal to
run without a TPU, and its one-chip phases at smoke size on the CPU
(Pallas kernel bodies in interpret mode)."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import transformer
from repro.models.api import get_config

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_reference_logits_match_the_model_in_float32(cs, monkeypatch):
    """The smoke's plain float32 forward and the model's own forward
    agree when the model also computes in float32."""
    monkeypatch.setenv("REPRO_PALLAS", "ref")
    cfg = dataclasses.replace(get_config("smollm-360m", smoke=True),
                              compute_dtype=jnp.float32)
    m = transformer.build(cfg)
    params = m.init(jax.random.key(3))
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 37)), jnp.int32)
    want = m.forward(params, {"tokens": toks})[0]
    got = cs.reference_logits(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert cs.rel_err(got, want) < 1e-5


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_fails_without_a_tpu():
    r = _run(ROOT, SMOKE)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_single_chip_phases_at_smoke_size(cs, monkeypatch, tmp_path):
    """Every check of the one-chip run passes on a smoke-size model:
    cold logits vs the reference, first token inside the load, warm
    joins, prefix hits, token identity over KV pages, and
    every main-path kernel dispatched through its Pallas body.  On the
    CPU every token stream is identical to ``reference_generate``."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    before = ops.registry.dispatch_snapshot()
    c = cs.Checks()
    s = cs.Smoke(get_config("smollm-360m", smoke=True), 0,
                 store_dir=str(tmp_path / "store"), n_new=12)
    try:
        c.phase("serve", cs.run_single, s)
    finally:
        s.cleanup()
    cs.check_kernels(c, "interpret", before)
    assert c.failed == []
    assert s.identical and all(n == m for n, m in s.identical.values())


@pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices: XLA_FLAGS="
           "--xla_force_host_platform_device_count=4")
def test_sharded_phase_on_host_devices(cs, monkeypatch, tmp_path):
    """The ``--chips 4`` phase on four host devices at smoke size, with
    the Pallas kernel bodies running under the mesh's ``shard_map``."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    c = cs.Checks()
    s = cs.Smoke(get_config("smollm-360m", smoke=True), 0,
                 store_dir=str(tmp_path / "store"), n_new=12)
    try:
        c.phase("sharded", cs.run_sharded, s, 4)
    finally:
        s.cleanup()
    assert c.failed == []
