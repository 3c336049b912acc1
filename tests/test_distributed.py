"""Distributed substrate: sharding-rule resolution, checkpoint
atomicity/retention/elasticity, gradient compression, straggler
detection, and a multi-device shard_map collective (subprocess)."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed.checkpoint import Checkpointer
from repro.distributed.compression import (dequantize_int8,
                                           init_error_feedback,
                                           make_error_feedback_transform,
                                           quantize_int8)
from repro.distributed.resilience import HeartbeatMonitor


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_guarded_spec_drops_nondivisible():
    mesh = _mesh11()
    rules = shd.ShardingRules({"heads": "model", "batch": "data"})
    # size-1 mesh axes resolve to replication (never crash)
    spec = shd._guarded_spec(mesh, rules, (4, 6), ("batch", "heads"))
    assert spec == P()


@given(dim=st.integers(1, 64))
def test_guarded_spec_divisibility(dim):
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = shd.ShardingRules({"x": "model"})
    spec = shd._guarded_spec(mesh, rules, (dim,), ("x",))
    # with mesh size 1 everything must be replicated
    assert spec == P()


def test_param_specs_cover_tree():
    from repro.models import transformer
    from repro.models.api import get_config
    cfg = get_config("mixtral-8x7b", smoke=True)
    m = transformer.build(cfg)
    ab = m.abstract()
    mesh = _mesh11()
    rules = shd.train_rules()
    specs = shd.param_specs(ab, mesh, rules)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(ab)


def test_constrain_noop_outside_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", "embed")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 2 ** 16), scale=st.floats(1e-3, 1e3))
def test_int8_quant_bound(seed, scale):
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(64) * scale,
                    jnp.float32)
    q, s = quantize_int8(x)
    deq = dequantize_int8(q, s)
    assert float(jnp.max(jnp.abs(deq - x))) <= float(s) / 2 + 1e-6


def test_error_feedback_unbiased_over_time():
    """Error feedback: the *sum* of compressed grads tracks the sum of
    true grads (residual stays bounded)."""
    r = np.random.default_rng(0)
    f = make_error_feedback_transform()
    g_true = {"w": jnp.asarray(r.standard_normal((32, 32)), jnp.float32)}
    ef = init_error_feedback(g_true)
    acc = jnp.zeros((32, 32))
    K = 50
    for _ in range(K):
        comp, ef = f(g_true, ef)
        acc = acc + comp["w"]
    err = np.abs(np.asarray(acc / K - g_true["w"])).max()
    # residual carry-over keeps the time-average within one quantum of truth
    q_step = float(jnp.max(jnp.abs(g_true["w"]))) / 127.0
    assert err < q_step * 2 / K * 50       # bounded by quantum
    assert float(jnp.max(jnp.abs(ef["w"]))) <= q_step  # residual bounded


def test_compressed_psum_multidevice_subprocess():
    """shard_map int8 all-gather reduce on 4 fake devices."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.distributed.compression import compressed_psum
mesh = jax.make_mesh((4,), ("dp",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)), jnp.float32)
f = shard_map(lambda xs: compressed_psum(xs[0], "dp")[None],
              mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
got = np.asarray(f(x))
want = np.asarray(jnp.mean(x, axis=0))
for row in got:
    np.testing.assert_allclose(row, want, atol=np.abs(x).max()/127.0 + 1e-6)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# checkpoint details
# ---------------------------------------------------------------------------

def test_checkpoint_retention_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"w": np.arange(8, dtype=np.float32)}
    for s in (1, 2, 3, 4):
        ck.save(s, {"w": tree["w"] * s})
    assert ck.all_steps() == [3, 4]        # retention
    assert ck.latest_step() == 4
    ab = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}
    step, params, _ = ck.restore(ab)
    assert step == 4
    np.testing.assert_array_equal(np.asarray(params["w"]), tree["w"] * 4)


def test_checkpoint_no_partial_state_on_interrupt(tmp_path):
    """A .tmp directory never shadows a completed checkpoint."""
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"w": np.ones(4, np.float32)})
    # simulate a crashed save: leftover tmp dir
    os.makedirs(str(tmp_path / "step_00000002.tmp"))
    assert ck.latest_step() == 1
    assert ck.all_steps() == [1]


def test_checkpoint_restore_with_shardings(tmp_path):
    """Elastic path: restore with explicit (single-device) shardings."""
    ck = Checkpointer(str(tmp_path))
    w = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
    ck.save(7, {"w": w})
    mesh = _mesh11()
    sh = {"w": jax.sharding.NamedSharding(mesh, P())}
    _, params, _ = ck.restore({"w": jax.ShapeDtypeStruct((8, 4),
                                                         jnp.float32)},
                              shardings=sh)
    np.testing.assert_array_equal(np.asarray(params["w"]), w)


# ---------------------------------------------------------------------------
# resilience
# ---------------------------------------------------------------------------

def test_straggler_detection():
    mon = HeartbeatMonitor(threshold=1.5, timeout_s=100.0)
    for step in range(5):
        for h in range(4):
            mon.report(f"host{h}", 1.0 if h != 2 else 3.0, now=float(step))
    assert mon.stragglers(now=5.0) == ["host2"]


def test_dead_host_detection():
    mon = HeartbeatMonitor(timeout_s=10.0)
    mon.report("a", 1.0, now=0.0)
    mon.report("b", 1.0, now=0.0)
    mon.report("a", 1.0, now=50.0)
    assert "b" in mon.stragglers(now=50.0)
    assert "a" not in mon.stragglers(now=50.0)
