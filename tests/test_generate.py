"""Generation-first serving: token-level equivalence of the
continuous-batching DecodeScheduler against the serial reference loop,
join/leave isolation, KV-overflow validation, typed router errors.

The load-bearing property: every token sequence produced through the
Router — cold (first token sampled inside the loading pipeline) or
warm, at any concurrency — is *bit-identical* to
``reference_generate``'s serial B=1 prefill + decode_step loop.
"""
import dataclasses
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import transformer
from repro.models.api import get_config
from repro.serving import (CacheOverflowError, DecodeScheduler,
                           GenerateSpec, InstancePool, Request, Router,
                           UnknownModelError, reference_generate)
from repro.store.store import WeightStore, deploy_model

CACHE_LEN = 64
PROMPT_LEN = 8

# dense / MoE / hybrid smoke archs (f32 so bit-identity is meaningful)
GEN_ARCHS = ["smollm-360m", "mixtral-8x7b", "recurrentgemma-2b"]


def _f32_cfg(arch):
    return dataclasses.replace(get_config(arch, smoke=True),
                               compute_dtype=jnp.float32)


def _prompt(cfg, seed):
    r = np.random.default_rng(seed)
    return r.integers(0, cfg.vocab_size, (PROMPT_LEN,)).astype(np.int32)


@pytest.fixture(scope="module")
def dense():
    """Model + params only (no store): scheduler-level tests."""
    cfg = _f32_cfg("smollm-360m")
    m = transformer.build(cfg)
    return cfg, m, m.init(jax.random.key(0))


# ---------------------------------------------------------------------------
# Router-level equivalence: cold + warm, concurrency 1 and N, per family
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", GEN_ARCHS)
def test_router_generation_bit_identical(arch, tmp_path):
    cfg = _f32_cfg(arch)
    m = transformer.build(cfg)
    store = WeightStore(str(tmp_path / "store"))
    deploy_model(store, m, arch, jax.random.key(0))
    example = {"tokens": jnp.asarray(_prompt(cfg, 99)[None])}
    pool = InstancePool(arch, lambda: (m, example), store,
                        strategy="cicada", max_instances=1,
                        gen_slots=4, gen_cache_len=CACHE_LEN)
    n_new = 6
    prompts = {i: _prompt(cfg, i) for i in range(6)}

    with Router({arch: pool}, workers=4) as router:
        # cold: first token produced by the loading pipeline itself
        r0 = router.submit(Request(req_id=0, model=arch,
                                   gen=GenerateSpec(prompt=prompts[0],
                                                    n_new=n_new))).result()
        assert r0.cold and r0.load_s > 0
        # TTFT inside the load: the first token is sampled within the
        # final unit's E event, before the pipeline ends
        load = pool._instances[0].last_load.trace
        e_last = next(e for e in load.events
                      if e.stage == "E" and e.layer == m.unit_names()[-1])
        t_first = r0.t_arrival + r0.ttft_s
        assert load.t0 < e_last.t_start < t_first < e_last.t_end \
            <= load.t_end
        # warm, concurrency 1
        r1 = router.submit(Request(req_id=1, model=arch,
                                   gen=GenerateSpec(prompt=prompts[1],
                                                    n_new=n_new))).result()
        assert not r1.cold and r1.ttft_s > 0
        # warm, concurrency 4: requests join one instance's batch
        futs = [router.submit(Request(req_id=i, model=arch,
                                      gen=GenerateSpec(prompt=prompts[i],
                                                       n_new=n_new)))
                for i in range(2, 6)]
        rest = [f.result(timeout=600) for f in futs]

    params = pool._instances[0].params
    for i, resp in enumerate([r0, r1] + rest):
        ref = reference_generate(m, params, prompts[i], n_new=n_new,
                                 cache_len=CACHE_LEN)
        assert list(resp.tokens) == ref, \
            f"{arch} req {i} (cold={resp.cold}) diverged from the " \
            f"serial reference"
        assert len(resp.tpot_s) == n_new - 1
        assert resp.ttft_s >= 0 and all(dt >= 0 for dt in resp.tpot_s)


# ---------------------------------------------------------------------------
# Scheduler-level: join/leave isolation, EOS, sampling determinism
# ---------------------------------------------------------------------------

def test_join_mid_batch_does_not_perturb_other_slots(dense):
    """A long generation in flight; a second request joins mid-batch:
    both must still match their solo serial references."""
    cfg, m, params = dense
    sched = DecodeScheduler(m, params, n_slots=4, cache_len=CACHE_LEN)
    pa, pb = _prompt(cfg, 1), _prompt(cfg, 2)
    out = {}

    def run_a():
        out["a"] = sched.generate(GenerateSpec(prompt=pa, n_new=24)).tokens

    ta = threading.Thread(target=run_a)
    ta.start()
    deadline = time.monotonic() + 120
    while sched.stats()["steps"] < 2:      # A's decode is running
        assert time.monotonic() < deadline, "A never started stepping"
        time.sleep(0.002)
    out["b"] = sched.generate(GenerateSpec(prompt=pb, n_new=6)).tokens
    ta.join(timeout=120)
    assert not ta.is_alive()

    assert out["a"] == reference_generate(m, params, pa, n_new=24,
                                          cache_len=CACHE_LEN)
    assert out["b"] == reference_generate(m, params, pb, n_new=6,
                                          cache_len=CACHE_LEN)
    assert sched.stats()["max_occupancy"] >= 2    # they truly overlapped
    assert sched.stats()["active"] == 0           # both left their slots


def test_leave_frees_slot_for_next_joiner(dense):
    """More requests than slots: later requests wait for a slot, then
    join — every sequence still matches its reference."""
    cfg, m, params = dense
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN)
    prompts = {i: _prompt(cfg, 10 + i) for i in range(4)}
    results = {}

    def run(i):
        results[i] = sched.generate(
            GenerateSpec(prompt=prompts[i], n_new=5)).tokens

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    for i in range(4):
        assert results[i] == reference_generate(
            m, params, prompts[i], n_new=5, cache_len=CACHE_LEN)
    assert sched.stats()["max_occupancy"] <= 2


def test_eos_leaves_early(dense):
    cfg, m, params = dense
    p = _prompt(cfg, 3)
    ref = reference_generate(m, params, p, n_new=8, cache_len=CACHE_LEN)
    eos = ref[2]                               # stop at the third token
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN)
    got = sched.generate(GenerateSpec(prompt=p, n_new=8,
                                      eos_id=int(eos))).tokens
    assert got == ref[:3]
    assert sched.stats()["active"] == 0


def test_sampled_generation_deterministic_and_matches_reference(dense):
    cfg, m, params = dense
    p = _prompt(cfg, 4)
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN)
    spec = GenerateSpec(prompt=p, n_new=6, temperature=0.8, seed=7)
    a = sched.generate(spec).tokens
    b = sched.generate(spec).tokens
    assert a == b                              # same seed -> same tokens
    assert a == reference_generate(m, params, p, n_new=6,
                                   cache_len=CACHE_LEN, temperature=0.8,
                                   seed=7)


# ---------------------------------------------------------------------------
# KV-cache overflow validation (an old silent bug)
# ---------------------------------------------------------------------------

def test_overflow_raises_instead_of_silent_wrap(dense):
    cfg, m, params = dense
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=16)
    with pytest.raises(CacheOverflowError):
        sched.generate(GenerateSpec(prompt=_prompt(cfg, 5), n_new=16))
    # validation happens before any slot is touched
    assert sched.stats()["active"] == 0 and sched.stats()["steps"] == 0


def test_max_len_clamps_n_new(dense):
    cfg, m, params = dense
    p = _prompt(cfg, 6)                        # 8-token prompt
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN)
    got = sched.generate(GenerateSpec(prompt=p, n_new=100,
                                      max_len=PROMPT_LEN + 4)).tokens
    assert len(got) == 4
    assert got == reference_generate(m, params, p, n_new=4,
                                     cache_len=CACHE_LEN)
    with pytest.raises(CacheOverflowError):    # no room to generate at all
        sched.generate(GenerateSpec(prompt=p, n_new=4,
                                    max_len=PROMPT_LEN))


# ---------------------------------------------------------------------------
# pool fairness: shared generation holds must not starve one-shot work
# ---------------------------------------------------------------------------

def test_oneshot_not_starved_by_generation_holds():
    """While an exclusive acquire() waits, no new generation joins are
    granted — resident generations drain and the one-shot wins, instead
    of a continuous joiner stream keeping the instance busy forever."""
    from test_router_pool import FakeInstance
    insts = []

    def factory():
        inst = FakeInstance(load_s=0.01)
        inst.gen_slots = 4
        insts.append(inst)
        return inst

    pool = InstancePool("m", builder=None, instance_factory=factory,
                        max_instances=1)
    inst = pool.acquire()
    inst.invoke({})                          # make it live
    pool.release(inst, logical_now=0.0, cold=True)

    gi, joinable = pool.acquire_gen()
    assert joinable and gi is inst

    # Router-style requeue gap: an exclusive acquire that TIMED OUT (no
    # longer parked in wait) keeps new joins paused via the sticky
    # starvation window until it retries and wins.
    with pytest.raises(TimeoutError):
        pool.acquire(timeout=0.02)
    with pytest.raises(TimeoutError):
        pool.acquire_gen(timeout=0.02)       # join refused in the gap
    got = {}

    def exclusive():
        got["inst"] = pool.acquire(timeout=10.0)

    t = threading.Thread(target=exclusive)
    t.start()
    deadline = time.monotonic() + 10
    while pool._excl_waiters == 0:           # exclusive is now parked
        assert time.monotonic() < deadline, "acquire never blocked"
        time.sleep(0.002)
    with pytest.raises(TimeoutError):        # new joins paused meanwhile
        pool.acquire_gen(timeout=0.05)
    pool.release_gen(gi, logical_now=0.0, cold=False)
    t.join(timeout=10)
    assert not t.is_alive() and got["inst"] is inst
    pool.release(got["inst"], logical_now=0.0)
    gi2, joinable2 = pool.acquire_gen(timeout=1.0)   # joins resume after
    assert joinable2
    pool.release_gen(gi2, logical_now=0.0)


# ---------------------------------------------------------------------------
# typed router errors (no jax needed)
# ---------------------------------------------------------------------------

def test_gen_join_resumes_after_starvation_window_expires():
    """An exclusive acquire that timed out and never retries must not
    block generation joins forever: a parked joiner wakes at the sticky
    window's expiry even though nothing notifies the CV."""
    from test_router_pool import FakeInstance
    insts = []

    def factory():
        inst = FakeInstance(load_s=0.01)
        inst.gen_slots = 4
        insts.append(inst)
        return inst

    pool = InstancePool("m", builder=None, instance_factory=factory,
                        max_instances=1)
    pool.EXCL_STARVATION_GRACE_S = 0.3
    inst = pool.acquire()
    inst.invoke({})
    pool.release(inst, logical_now=0.0, cold=True)
    gi, _ = pool.acquire_gen()
    with pytest.raises(TimeoutError):        # arms the sticky window
        pool.acquire(timeout=0.02)
    pool.release_gen(gi, logical_now=0.0, cold=False)  # instance idle+live
    t0 = time.monotonic()
    gi2, joinable = pool.acquire_gen(timeout=30.0)
    assert joinable and gi2 is inst
    assert time.monotonic() - t0 < 5.0       # woke at ~0.3 s, not 30 s
    pool.release_gen(gi2, logical_now=0.0)


def test_cancelled_future_does_not_kill_worker():
    """A request cancelled while queued is dropped at dispatch time —
    the worker must survive (set_result on a cancelled future raises)
    and keep serving later submissions."""
    from test_router_pool import fake_pool, _req
    pool = fake_pool(max_instances=1, load_s=0.2)
    with Router({"m": pool}, workers=1) as router:
        blocker = router.submit(_req(0))
        deadline = time.monotonic() + 5
        while pool.stats().busy < 1:         # worker inside the load
            assert time.monotonic() < deadline
            time.sleep(0.005)
        victim = router.submit(_req(1))
        assert victim.cancel()
        after = router.submit(_req(2))       # must still be served
        blocker.result(timeout=10)
        assert after.result(timeout=10).req_id == 2
        assert victim.cancelled()


def test_unknown_model_typed_error_on_submitting_thread():
    from test_router_pool import fake_pool
    with Router({"m": fake_pool()}, workers=1) as router:
        with pytest.raises(UnknownModelError, match="nope"):
            router.submit(Request(req_id=0, model="nope", batch={}))
        assert isinstance(UnknownModelError("x"), KeyError)  # compat
        # the failed submit left no queued work behind
        assert router.stats.submitted == 0
    # generation requests fail the same way, before any worker sees them
    with Router({"m": fake_pool()}, workers=1) as router:
        with pytest.raises(UnknownModelError):
            router.submit(Request(req_id=1, model="nope",
                                  gen=GenerateSpec(prompt=[1, 2, 3])))


# ---------------------------------------------------------------------------
# kernel-registry wiring: serving exercises the Pallas kernel bodies
# ---------------------------------------------------------------------------

def test_scheduler_runs_interpret_kernels_bit_identical(dense, monkeypatch):
    """The DecodeScheduler's jitted prefill/step dispatch the *Pallas
    kernel bodies* (flash prefill, paged decode attention, the in-place
    page write) — the registry records the dispatches — and the token
    stream stays bit-identical to the serial reference, whose
    contiguous-cache decode kernel is traced under the same mode.  Default (and any non-TPU run): interpret
    mode; with REPRO_PALLAS=pallas on a TPU the same assertions hold
    against the real Mosaic lowerings."""
    import os

    from repro.kernels import ops

    cfg, m, params = dense
    mode = os.environ.get("REPRO_PALLAS")
    if mode != "pallas":
        mode = "interpret"
    monkeypatch.setenv("REPRO_PALLAS", mode)
    before = ops.registry.dispatch_snapshot()
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN)
    served = ("flash_attention", "decode_attention_paged",
              "kv_pages_write")
    for kern in served + ("decode_attention",):
        assert sched.kernel_modes[kern] == mode, kern
    spec = GenerateSpec(prompt=_prompt(cfg, 5), n_new=4)
    got = sched.generate(spec).tokens
    after = ops.registry.dispatch_snapshot()
    for kern in served:
        assert after.get((kern, mode), 0) > \
            before.get((kern, mode), 0), kern
    want = reference_generate(m, params, spec.prompt, n_new=4,
                              cache_len=CACHE_LEN)
    assert got == want
    ref = ops.registry.dispatch_snapshot()
    assert ref.get(("decode_attention", mode), 0) > \
        after.get(("decode_attention", mode), 0)


def test_registry_auto_probes_and_forces(monkeypatch):
    """auto resolves by backend (ref off a TPU, where nothing is
    compiled); set_mode overrides the env var; bogus modes fail
    loudly."""
    from repro.kernels import ops

    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    desc = ops.registry.describe()
    assert set(desc) == {"flash_attention", "decode_attention",
                         "decode_attention_paged", "kv_pages_write",
                         "ssd_scan", "rglru_scan", "weight_transform",
                         "quant_matmul"}
    if jax.default_backend() != "tpu":
        assert all(not d["compiled"] for d in desc.values())
        assert all(d["mode"] == "ref" for d in desc.values())
        assert not ops.registry.pallas_supported("flash_attention")
    monkeypatch.setenv("REPRO_PALLAS", "xla")       # legacy alias
    assert ops.registry.mode("flash_attention") == "ref"
    ops.set_mode("interpret")                       # flag beats env
    try:
        assert ops.registry.mode("flash_attention") == "interpret"
        assert ops.registry.fingerprint()[0] == "interpret"
        assert ops.registry.modes()["flash_attention"] == "interpret"
    finally:
        ops.set_mode(None)
    with pytest.raises(ValueError, match="must be one of"):
        ops.set_mode("vulkan")


def test_registry_probe_failure_on_tpu_raises(monkeypatch):
    """On a TPU backend a kernel whose probe fails to compile raises
    with the compiler's message at dispatch — auto never downgrades it
    to ``ref``.  A kernel that compiles is checked once and counted."""
    from repro.kernels import ops

    def broken():
        raise NotImplementedError("Unimplemented primitive: cumsum")

    calls = []
    reg = ops.KernelRegistry()
    reg.register(ops.KernelSpec("broken", None, broken))
    reg.register(ops.KernelSpec("fine", None, lambda: calls.append(1)))
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert reg.mode("broken") == "pallas"
    with pytest.raises(RuntimeError, match="cumsum"):
        reg.dispatch("broken")
    with pytest.raises(RuntimeError, match="'broken' failed to compile"):
        reg.pallas_supported("broken")
    assert reg.dispatch("fine") == "pallas"
    assert reg.dispatch("fine") == "pallas"
    assert calls == [1]
    assert reg.describe() == {"broken": {"mode": "pallas",
                                         "compiled": False},
                              "fine": {"mode": "pallas", "compiled": True}}
    assert reg.dispatch_snapshot() == {("fine", "pallas"): 2}
