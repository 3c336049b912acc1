"""Paged KV cache: KVPagePool allocator semantics (refcounts, prefix
index, cached LRU, copy-on-write, blocking backpressure), paged
DecodeScheduler token-equivalence against ``reference_generate``,
page-budget admission of mixed prompt lengths that overflow the slotted
arena, and physical page sharing across requests with a common prompt
prefix.

The pool storm test exercises the allocator from many threads with
``check_invariants`` between operations — CI runs this file under
``REPRO_ANALYZE=1`` so the lock/condition discipline is probed too.
"""
import dataclasses
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.metrics import MetricsRegistry
from repro.models import transformer
from repro.models.api import get_config
from repro.serving import (CacheOverflowError, DecodeScheduler,
                           GenerateSpec, reference_generate)
from repro.serving.decode import paged_page_count, validate_spec_paged
from repro.serving.kvpages import KVPagePool, page_hashes

CACHE_LEN = 64
PT = 16                                    # page tokens for scheduler tests

GEN_ARCHS = ["smollm-360m", "mixtral-8x7b", "recurrentgemma-2b"]


def _f32_cfg(arch, **over):
    return dataclasses.replace(get_config(arch, smoke=True),
                               compute_dtype=jnp.float32, **over)


def _prompt(cfg, seed, n=8):
    r = np.random.default_rng(seed)
    return r.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


@pytest.fixture(scope="module")
def dense():
    cfg = _f32_cfg("smollm-360m")
    m = transformer.build(cfg)
    return cfg, m, m.init(jax.random.key(0))


# ---------------------------------------------------------------------------
# page_hashes
# ---------------------------------------------------------------------------

def test_page_hashes_running_and_partial():
    toks = np.arange(40, dtype=np.int32)
    hs = page_hashes("m", toks, 16)
    assert len(hs) == 2                    # trailing partial page unhashed
    # running: page 1's digest commits to page 0 too
    assert page_hashes("m", toks[:32], 16) == hs
    other = toks.copy()
    other[0] = 999
    assert page_hashes("m", other, 16)[1] != hs[1]
    # model identity prefixes the hash
    assert page_hashes("other", toks, 16) != hs


# ---------------------------------------------------------------------------
# KVPagePool unit semantics
# ---------------------------------------------------------------------------

def test_pool_alloc_release_refcount():
    pool = KVPagePool(n_pages=4, page_tokens=8)
    ids = pool.alloc(3)
    assert len(ids) == 3 and len(set(ids)) == 3
    st = pool.stats()
    assert (st.pinned, st.free) == (3, 1)
    pool.release(ids[:1])
    assert pool.stats().free == 2          # unregistered page -> free list
    pool.release(ids[1:])
    st = pool.stats()
    assert (st.pinned, st.free, st.used) == (0, 4, 0)
    pool.check_invariants()


def test_pool_never_fits_is_error_smaller_is_backpressure():
    pool = KVPagePool(n_pages=2, page_tokens=8)
    with pytest.raises(CacheOverflowError):
        pool.alloc(3)                      # can never fit: typed error
    held = pool.alloc(2)
    with pytest.raises(TimeoutError):      # fits, pool busy: backpressure
        pool.alloc(1, timeout=0.05)
    got = []
    t = threading.Thread(target=lambda: got.extend(pool.alloc(2)))
    t.start()
    time.sleep(0.05)
    assert not got                         # still blocked
    pool.release(held)
    t.join(timeout=5)
    assert len(got) == 2
    pool.check_invariants()


def test_pool_prefix_register_match_and_lru():
    pool = KVPagePool(n_pages=3, page_tokens=4)
    hs = page_hashes("m", np.arange(8, dtype=np.int32), 4)
    ids = pool.alloc(2)
    for pid, h in zip(ids, hs):
        pool.register(pid, h)
    pool.release(ids)                      # registered -> cached, not free
    st = pool.stats()
    assert (st.cached, st.free) == (2, 1)
    hit = pool.match_prefix(hs)
    assert hit == ids                      # revived in order, pinned
    assert pool.stats().prefix_hits == 2
    # a miss stops the walk and counts once
    assert pool.match_prefix(["nope"]) == []
    assert pool.stats().prefix_misses == 1
    pool.release(hit)
    # pressure evicts cached LRU pages and invalidates their hashes
    big = pool.alloc(3)
    assert pool.match_prefix(hs) == []
    pool.release(big)
    pool.check_invariants()


def test_pool_copy_on_write_fork():
    pool = KVPagePool(n_pages=2, page_tokens=4)
    (pid,) = pool.alloc(1)
    assert pool.ensure_writable(pid) == (pid, False)   # sole holder
    pool.register(pid, "h0")
    pool.release(pid_list := [pid])
    hit = pool.match_prefix(["h0"])        # now shared: us + index
    hit2 = pool.match_prefix(["h0"])       # refcount 2
    new, copied = pool.ensure_writable(pid)
    assert copied and new != pid
    assert pool.stats().cow_copies == 1
    pool.release([new] + hit2)
    pool.release(hit)
    pool.check_invariants()
    del pid_list


def test_pool_storm_invariants():
    """Many threads alloc/release/register/match concurrently; the
    page partition invariant holds throughout (run under
    REPRO_ANALYZE=1 in CI to probe the locking too)."""
    pool = KVPagePool(n_pages=16, page_tokens=4)
    toks = np.arange(64, dtype=np.int32)
    hs = page_hashes("m", toks, 4)
    stop = threading.Event()
    errors = []

    def worker(seed):
        r = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                n = int(r.integers(1, 4))
                try:
                    ids = pool.alloc(n, timeout=0.2)
                except TimeoutError:
                    continue
                if r.random() < 0.5:
                    for j, pid in enumerate(ids):
                        pool.register(pid, hs[int(r.integers(len(hs)))])
                hit = pool.match_prefix(hs[:int(r.integers(1, 4))])
                pool.check_invariants()
                pool.release(hit)
                pool.release(ids)
                pool.check_invariants()
        except BaseException as e:          # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    pool.check_invariants()


# ---------------------------------------------------------------------------
# validate_spec_paged
# ---------------------------------------------------------------------------

def test_validate_spec_paged_message_and_typing():
    spec = GenerateSpec(prompt=[1, 2, 3], n_new=100)
    with pytest.raises(CacheOverflowError) as ei:
        validate_spec_paged(spec, 3, page_tokens=8, n_pages=4)
    msg = str(ei.value)
    assert "4 pages x 8 tokens" in msg and "32 tokens" in msg
    pool = KVPagePool(n_pages=4, page_tokens=8)
    held = pool.alloc(3)
    with pytest.raises(CacheOverflowError) as ei:
        validate_spec_paged(spec, 3, page_tokens=8, n_pages=4,
                            stats=pool.stats())
    assert "live occupancy 3/4 pages" in str(ei.value)
    pool.release(held)
    # fitting requests never raise here, whatever the live occupancy
    assert validate_spec_paged(GenerateSpec(prompt=[1], n_new=8), 1,
                               page_tokens=8, n_pages=4) == 8


def test_paged_page_count_budget(dense):
    _, m, _ = dense
    per = m.kv_page_bytes(PT)
    assert per > 0
    assert paged_page_count(m, page_tokens=PT,
                            budget_bytes=10 * per + 3) == 10
    # no byte budget -> the slotted arena's worth of pages
    assert paged_page_count(m, page_tokens=PT, n_slots=4,
                            cache_len=CACHE_LEN) == 4 * (CACHE_LEN // PT)
    with pytest.raises(ValueError):
        paged_page_count(m, page_tokens=PT, budget_bytes=per - 1)


# ---------------------------------------------------------------------------
# scheduler equivalence: paged == reference, per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", GEN_ARCHS)
def test_paged_scheduler_bit_identical(arch):
    """Concurrent mixed-length paged generation matches the serial
    reference token-for-token (dense fully paged; MoE/hybrid smoke
    configs keep ring/SSM states slot-resident — the paged scheduler
    must preserve their semantics unchanged)."""
    cfg = _f32_cfg(arch)
    m = transformer.build(cfg)
    params = m.init(jax.random.key(0))
    sched = DecodeScheduler(m, params, n_slots=3, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN)
    lens = (5, 8, 19)
    prompts = [_prompt(cfg, i, n=lens[i]) for i in range(3)]
    results = [None] * 3

    def run(i):
        results[i] = sched.generate(
            GenerateSpec(prompt=prompts[i], n_new=7, seed=i,
                         temperature=0.5 if i == 2 else 0.0))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(3):
        ref = reference_generate(m, params, prompts[i], n_new=7,
                                 cache_len=CACHE_LEN, seed=i,
                                 temperature=0.5 if i == 2 else 0.0)
        assert results[i].tokens == ref, (arch, i)
    st = sched.kvpool.stats()
    assert st.pinned == 0, st              # every page released on leave
    sched.kvpool.check_invariants()


def test_paged_admits_mixed_lengths_beyond_slotted_ceiling(dense):
    """N requests whose prompts overflow the slotted per-slot arena all
    admit and complete under the *same* byte budget paged."""
    cfg, m, params = dense
    n_slots, cache_len = 2, 32
    slotted = DecodeScheduler(m, params, n_slots=n_slots,
                              cache_len=cache_len)
    long_prompt = _prompt(cfg, 42, n=40)   # 40 + 8 > 32: slotted rejects
    with pytest.raises(CacheOverflowError):
        slotted.generate(GenerateSpec(prompt=long_prompt, n_new=8))
    # same budget, paged: n_slots * cache_len = 64 tokens = 8 x 8-token
    # pages shared across residents instead of 32 per slot
    paged = DecodeScheduler(m, params, n_slots=n_slots,
                            cache_len=cache_len, kv_page_tokens=8,
                            kv_max_seq=2 * cache_len,
                            kv_budget_bytes=n_slots * cache_len // 8
                            * m.kv_page_bytes(8))
    assert paged.n_pages == 8
    prompts = [long_prompt, _prompt(cfg, 43, n=9)]
    results = [None] * 2

    def run(i):
        results[i] = paged.generate(
            GenerateSpec(prompt=prompts[i], n_new=8, seed=i))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(2):
        ref = reference_generate(m, params, prompts[i], n_new=8,
                                 cache_len=64, seed=i)
        assert results[i].tokens == ref, i
    paged.kvpool.check_invariants()


def test_paged_moe_full_attention_prefix():
    """A full-attention MoE variant pages its KV and shares prefixes."""
    cfg = _f32_cfg("mixtral-8x7b", sliding_window=0)
    m = transformer.build(cfg)
    assert m.supports_prefix_cache
    params = m.init(jax.random.key(0))
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN)
    shared = _prompt(cfg, 7, n=2 * PT)
    pa = np.concatenate([shared, _prompt(cfg, 8, n=5)])
    ra = sched.generate(GenerateSpec(prompt=pa, n_new=5, seed=1))
    pb = np.concatenate([shared, _prompt(cfg, 9, n=3)])
    rb = sched.generate(GenerateSpec(prompt=pb, n_new=5, seed=2))
    assert sched.kvpool.stats().prefix_hits == 2
    assert ra.tokens == reference_generate(m, params, pa, n_new=5,
                                           cache_len=CACHE_LEN, seed=1)
    assert rb.tokens == reference_generate(m, params, pb, n_new=5,
                                           cache_len=CACHE_LEN, seed=2)


# ---------------------------------------------------------------------------
# physical prefix sharing
# ---------------------------------------------------------------------------

def test_shared_prefix_pins_same_physical_pages(dense):
    """Requests with a common system prompt reuse its pages: the pool's
    used-page count grows by the unshared suffix only, and the sharing
    request's tokens are bit-identical to its serial reference."""
    cfg, m, params = dense
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN)
    shared = _prompt(cfg, 100, n=2 * PT)               # 2 full pages
    pa = np.concatenate([shared, _prompt(cfg, 101, n=6)])
    ra = sched.generate(GenerateSpec(prompt=pa, n_new=6, seed=3))
    st_a = sched.kvpool.stats()
    assert st_a.prefix_hits == 0
    pb = np.concatenate([shared, _prompt(cfg, 102, n=4)])
    rb = sched.generate(GenerateSpec(prompt=pb, n_new=6, seed=4))
    st_b = sched.kvpool.stats()
    # B needed ceil((36+6)/16) = 3 pages but pinned only 1 new one: the
    # pool's live page count is below the sum of per-request needs
    assert st_b.prefix_hits == 2
    assert st_b.used - st_a.used <= 1
    assert ra.tokens == reference_generate(m, params, pa, n_new=6,
                                           cache_len=CACHE_LEN, seed=3)
    assert rb.tokens == reference_generate(m, params, pb, n_new=6,
                                           cache_len=CACHE_LEN, seed=4)
    sched.kvpool.check_invariants()


def test_identical_prompt_full_hit_still_generates(dense):
    """The hit cap always leaves a non-empty prefill suffix — an exactly
    page-aligned identical prompt must not degenerate to a zero-token
    prefill."""
    cfg, m, params = dense
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN)
    p = _prompt(cfg, 55, n=2 * PT)                     # page-aligned
    ref = reference_generate(m, params, p, n_new=5, cache_len=CACHE_LEN,
                             seed=9)
    for _ in range(2):                                 # cold, then warm hit
        r = sched.generate(GenerateSpec(prompt=p, n_new=5, seed=9))
        assert r.tokens == ref
    assert sched.kvpool.stats().prefix_hits == 1       # capped at S-1 page


# ---------------------------------------------------------------------------
# metrics surface
# ---------------------------------------------------------------------------

def test_kv_metrics_wired(dense):
    cfg, m, params = dense
    reg = MetricsRegistry()
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN,
                            metrics=reg)
    sched.generate(GenerateSpec(prompt=_prompt(cfg, 1, n=PT + 1), n_new=4))
    snap = reg.snapshot()
    assert snap["gauges"]["kv/pages_total"]["value"] == sched.n_pages
    assert "kv/pages_used" in snap["gauges"]
    assert "kv/pages_pinned" in snap["gauges"]
    assert snap["counters"]["kv/prefix_misses"] >= 1
    st = sched.stats()
    assert st["kv_pages_total"] == sched.n_pages
    assert st["kv_page_tokens"] == PT


def test_paged_leave_resets_pos_and_counts_attended_pages(dense):
    """A leaver's slot goes back to position 0 on the scratch page (a
    dead row costs the paged kernel one page), and every batched step
    adds the pages the kernel fetches, sum(pos // pt + 1) over all rows,
    to ``decode/kv_pages_attended``."""
    cfg, m, params = dense
    reg = MetricsRegistry()
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN,
                            metrics=reg)
    attended = reg.counter("decode/kv_pages_attended")
    seen = []                               # (pos, counter before step)
    step = sched._pstep

    def spy(params, cache, pools, tables, tok, pos, *rest):
        seen.append((np.asarray(pos).copy(), attended.value))
        return step(params, cache, pools, tables, tok, pos, *rest)

    sched._pstep = spy
    sched.generate(GenerateSpec(prompt=_prompt(cfg, 1, n=2 * PT + 3),
                                n_new=4))
    assert sched._pos.tolist() == [0, 0]
    assert (sched._tables == sched.kvpool.scratch_id).all()
    sched.generate(GenerateSpec(prompt=_prompt(cfg, 2, n=PT - 1), n_new=3))
    assert sched._pos.tolist() == [0, 0]
    assert len(seen) == 3 + 2 == reg.counter("decode/steps").value
    after = [c for _, c in seen[1:]] + [attended.value]
    for (pos, before), a in zip(seen, after):
        assert a - before == int((pos // PT + 1).sum()), (pos, before, a)
    # each request decodes from its prompt's end in slot 0; the idle
    # slot sits at position 0 throughout
    assert seen[0][0].tolist() == [2 * PT + 3, 0]
    assert seen[3][0].tolist() == [PT - 1, 0]


def test_pack_and_prefill_donate_their_buffers(dense):
    """Admission holds one page pool and one cache per request on the
    device: a pack consumes the pool it writes into, and a prefill the
    empty cache it fills, so the joins of one step boundary never
    allocate a pool each while they wait in the device queue.  A
    prefix hit, which reads the pool, is dispatched before a later
    pack consumes it, and still generates its reference tokens."""
    cfg, m, params = dense
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN)
    seen = {"pack": [], "prefill": []}
    pack, prefill = sched._pack, sched._prefill

    def spy_pack(pools, cache, ids, first):
        seen["pack"].append(jax.tree.leaves(pools))
        return pack(pools, cache, ids, first)

    def spy_prefill(p, batch, cache):
        seen["prefill"].append(jax.tree.leaves(cache))
        return prefill(p, batch, cache)

    sched._pack, sched._prefill = spy_pack, spy_prefill
    shared = _prompt(cfg, 200, n=2 * PT)
    prompts = [np.concatenate([shared, _prompt(cfg, 201 + i, n=5)])
               for i in range(3)]
    out = [None] * len(prompts)

    def go(i):
        out[i] = sched.generate(GenerateSpec(prompt=prompts[i], n_new=5,
                                             seed=i))

    go(0)
    threads = [threading.Thread(target=go, args=(i,)) for i in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(seen["pack"]) == 3 and len(seen["prefill"]) == 1
    for leaves in seen["pack"] + seen["prefill"]:
        assert all(x.is_deleted() for x in leaves)
    assert sched.kvpool.stats().prefix_hits == 4
    for i, p in enumerate(prompts):
        assert out[i].tokens == reference_generate(
            m, params, p, n_new=5, cache_len=CACHE_LEN, seed=i)
    sched.kvpool.check_invariants()


@pytest.mark.parametrize("mode,arch", [
    ("ref", "smollm-360m"),
    ("interpret", "smollm-360m"),
    # pattern (attn, rglru) over 3 layers: a paged tail group
    ("interpret", "attn-tail"),
])
def test_step_consumes_the_pools_it_was_given(monkeypatch, mode, arch):
    """A decode step donates the page pools: each layer writes its rows
    into them in place (the Pallas write in interpret mode), so every
    pool leaf a step was handed is deleted once it is dispatched, and
    the scheduler holds the step's output.  A prefix hit's gather,
    dispatched while other requests step, still reads live pools; every
    request generates its serial reference's tokens."""
    monkeypatch.setenv("REPRO_PALLAS", mode)
    if arch == "attn-tail":
        cfg = _f32_cfg("recurrentgemma-2b", block_pattern=("attn", "rglru"),
                       n_layers=3)
    else:
        cfg = _f32_cfg(arch)
    m = transformer.build(cfg)
    assert m.tail_kinds == (("attn",) if arch == "attn-tail" else ())
    params = m.init(jax.random.key(0))
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=CACHE_LEN,
                            kv_page_tokens=PT, kv_max_seq=CACHE_LEN)
    seen = []
    step = sched._pstep

    def spy(params, cache, pools, *rest):
        seen.append(jax.tree.leaves(pools))
        return step(params, cache, pools, *rest)

    sched._pstep = spy
    shared = _prompt(cfg, 300, n=2 * PT)
    prompts = [np.concatenate([shared, _prompt(cfg, 301 + i, n=3 + i)])
               for i in range(3)]
    out = [None] * len(prompts)

    def go(i):
        out[i] = sched.generate(GenerateSpec(prompt=prompts[i], n_new=6,
                                             seed=i))

    go(0)
    threads = [threading.Thread(target=go, args=(i,)) for i in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(seen) >= 5 and all(seen)
    assert all(x.is_deleted() for leaves in seen for x in leaves)
    assert not any(x.is_deleted() for x in jax.tree.leaves(sched._kvpages))
    if m.supports_prefix_cache:
        assert sched.kvpool.stats().prefix_hits == 4
    for i, p in enumerate(prompts):
        assert out[i].tokens == reference_generate(
            m, params, p, n_new=6, cache_len=CACHE_LEN, seed=i), i
    sched.kvpool.check_invariants()


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("arch", ["smollm-360m", "attn-tail"])
def test_pack_gather_and_copy_page_round_trip(monkeypatch, mode, arch):
    """The device halves of admission on the lane-dense pool, stacked and
    tail groups alike: pages packed from a request's cache, forked by
    ``copy_page`` and gathered back give that cache's rows exactly, and
    leave every other page as it was."""
    monkeypatch.setenv("REPRO_PALLAS", mode)
    if arch == "attn-tail":
        cfg = _f32_cfg("recurrentgemma-2b", block_pattern=("attn", "rglru"),
                       n_layers=3)
    else:
        cfg = _f32_cfg(arch)
    m = transformer.build(cfg)
    n_pp = 3
    cache = m.init_request_cache(n_pp * PT, CACHE_LEN)
    r = np.random.default_rng(5)
    cache = jax.tree.map(lambda a: jnp.asarray(
        r.standard_normal(a.shape), a.dtype), cache)
    pools = m.init_kv_pages(8, PT)
    before = jax.tree.map(np.asarray, pools)
    ids = jnp.asarray([6, 1, 4], jnp.int32)
    pools = jax.jit(m.pack_pages, static_argnums=3)(pools, cache, ids[1:], 1)
    pools = m.copy_page(pools, 4, 3)
    back = m.gather_pages(m.init_request_cache(n_pp * PT, CACHE_LEN), pools,
                          jnp.asarray([0, 1, 3], jnp.int32))
    for key, kind, stacked in m._cache_groups():
        if pools[key] is None:
            continue
        pool = np.asarray(pools[key] if stacked else pools[key][None])
        untouched = [0, 2, 5, 6, 7]
        np.testing.assert_array_equal(
            pool[:, untouched],
            (before[key] if stacked else before[key][None])[:, untouched])
        for n in ("k", "v"):
            want = np.asarray(cache[key][n])[..., PT:, :]    # pages 1, 2
            got = np.asarray(back[key][n])[..., PT:, :]
            np.testing.assert_array_equal(got, want)
