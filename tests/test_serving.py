"""Serverless platform: cold/warm lifecycle, keep-alive eviction,
trace generation, batched decode against full forwards."""
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import transformer
from repro.models.api import get_config
from repro.serving.api import GenerateSpec
from repro.serving.engine import (DecodeScheduler, FunctionInstance,
                                  ServerlessPlatform)
from repro.serving.trace import Invocation, azure_like_trace, summarize
from repro.store.store import WeightStore, deploy_model


@pytest.fixture(scope="module")
def deployed(tmp_path_factory):
    d = tmp_path_factory.mktemp("store")
    cfg = get_config("smollm-360m", smoke=True)
    m = transformer.build(cfg)
    store = WeightStore(str(d))
    deploy_model(store, m, "smollm-360m", jax.random.key(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8)),
        jnp.int32)}
    return store, m, cfg, batch


@pytest.mark.slow
def test_cold_then_warm(deployed):
    store, m, cfg, batch = deployed
    inst = FunctionInstance(m, "smollm-360m", store, strategy="cicada",
                            example_batch=batch)
    logits1, info1 = inst.invoke(batch)
    assert info1["cold"] and info1["load_s"] > 0
    logits2, info2 = inst.invoke(batch)
    assert not info2["cold"] and info2["infer_s"] > 0
    np.testing.assert_allclose(np.asarray(logits1, np.float32),
                               np.asarray(logits2, np.float32),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_eviction_forces_cold_start(deployed):
    store, m, cfg, batch = deployed
    inst = FunctionInstance(m, "smollm-360m", store, example_batch=batch)
    inst.invoke(batch)
    assert inst.live
    inst.evict()
    assert not inst.live
    _, info = inst.invoke(batch)
    assert info["cold"]


@pytest.mark.slow
def test_platform_trace_replay(deployed):
    store, m, cfg, batch = deployed
    builders = {"smollm-360m": lambda: (m, batch)}
    platform = ServerlessPlatform(store, builders, strategy="cicada",
                                  keep_alive_s=120.0)
    trace = [Invocation(0.0, "smollm-360m", 0),
             Invocation(1.0, "smollm-360m", 1),
             Invocation(300.0, "smollm-360m", 2)]   # past keep-alive
    out = platform.run_trace(trace, lambda name: batch)
    assert [r.cold for r in out] == [True, False, True]
    assert all(r.latency_s > 0 for r in out)


@pytest.mark.slow
def test_platform_concurrent_replay(deployed):
    """run_trace(concurrency=4): concurrent cold starts scale the pool
    out, responses keep trace order and gain queueing delay."""
    store, m, cfg, batch = deployed
    builders = {"smollm-360m": lambda: (m, batch)}
    platform = ServerlessPlatform(store, builders, strategy="cicada",
                                  keep_alive_s=1000.0, max_instances=2)
    trace = [Invocation(0.0, "smollm-360m", i) for i in range(4)]
    out = platform.run_trace(trace, lambda name: batch, concurrency=4)
    assert [r.req_id for r in out] == [0, 1, 2, 3]
    assert sum(r.cold for r in out) == 2          # one per instance
    assert all(r.queue_s >= 0 for r in out)
    assert all(r.latency_s > 0 for r in out)
    ps = platform.pool_stats()["smollm-360m"]
    assert ps.size == 2
    assert ps.cold_starts == 2 and ps.warm_hits == 2
    assert platform.last_router_stats.submitted == 4
    assert platform.last_router_stats.completed == 4


def test_trace_generator_statistics():
    tr = azure_like_trace(duration_s=3600.0, n_invocations=2426,
                          models=["a", "b", "c"], seed=0)
    s = summarize(tr)
    assert s["n"] == 2426                      # exact count (paper Sec IV-B)
    assert s["burst_ratio"] > 2.0              # bursty like Fig. 8
    ts = [i.t for i in tr]
    assert ts == sorted(ts)
    assert 0 <= min(ts) and max(ts) <= 3600.0
    assert {i.model for i in tr} == {"a", "b", "c"}
    # deterministic
    tr2 = azure_like_trace(duration_s=3600.0, n_invocations=2426,
                           models=["a", "b", "c"], seed=0)
    assert [(i.t, i.model) for i in tr] == [(i.t, i.model) for i in tr2]


@pytest.mark.slow
def test_batched_decode_matches_stepwise_forward():
    """Two concurrent greedy generations through the scheduler, batched
    over KV pages == argmax over repeated full forwards."""
    import dataclasses
    cfg = dataclasses.replace(get_config("smollm-360m", smoke=True),
                              compute_dtype=jnp.float32)
    m = transformer.build(cfg)
    params = m.init(jax.random.key(1))
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=64)
    prompt = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    rows = [None, None]

    def run(b):
        rows[b] = sched.generate(GenerateSpec(prompt=prompt[b],
                                              n_new=5)).tokens

    threads = [threading.Thread(target=run, args=(b,)) for b in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    gen = jnp.asarray(rows, jnp.int32)
    assert gen.shape == (2, 5)
    # oracle: greedy over repeated full forwards
    toks = prompt
    expect = []
    for _ in range(5):
        lg, _ = m.forward(params, {"tokens": toks})
        nxt = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
        expect.append(nxt)
        toks = jnp.concatenate([toks, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(gen),
                                  np.asarray(jnp.concatenate(expect, 1)))
