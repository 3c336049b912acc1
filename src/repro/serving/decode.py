"""Slot-based continuous-batching decode engine.

One :class:`DecodeScheduler` is owned by each live
:class:`~repro.serving.pool.FunctionInstance`.  It holds a single
fixed-capacity *slotted* KV cache — ``init_cache(n_slots, cache_len)``
— and decodes every resident generation request with one shared jitted
step, whatever the slot occupancy:

  * a request **joins** at a step boundary: its prompt is prefilled into
    a fresh ``B=1`` cache on the calling thread, then merged into a free
    slot between two batch steps (an in-flight step never observes a
    half-written slot);
  * a request **leaves** on completion or EOS, freeing its slot for the
    next joiner — requests arriving at different times batch dynamically
    instead of serializing;
  * the batched step is **cooperatively driven**: every caller thread
    blocked in :meth:`generate` is eligible to run the next step, so the
    engine needs no dedicated decode thread and quiesces for free when
    no request is resident.

Correctness invariant (enforced by tests/test_generate.py): each
request's token sequence is *bit-identical* to :func:`reference_generate`
— a serial ``prefill`` + ``decode_step`` loop at ``B=1`` — because every
per-slot computation (attention over its own cache rows, per-row MoE
dispatch, SSM/RG-LRU state updates, sampling keyed by seed+position) is
independent of what the other slots hold.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import analysis, metrics as metrics_mod
from repro.kernels import ops
from repro.serving import kvpages
from repro.serving.api import CacheOverflowError, GenerateSpec

PyTree = Any

# the scheduler's profiler host spans (``decode.step`` ...), on the
# device trace's clock
_span = jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# sampling — one rule shared by the batched step, the first-token path
# (warm prefill AND the in-pipeline cold path) and the serial reference
# ---------------------------------------------------------------------------

def sample_tokens(logits: jax.Array, seed: jax.Array, next_pos: jax.Array,
                  temperature: jax.Array) -> jax.Array:
    """Per-row next-token choice.  logits: (B, V); seed/next_pos/
    temperature: (B,).  temperature == 0 -> greedy argmax; > 0 ->
    categorical over logits/temperature keyed by fold_in(seed, next_pos)
    — deterministic per request and independent of co-resident rows.
    """
    def _row(lg, sd, p, t):
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(sd), p)
        scaled = lg.astype(jnp.float32) / jnp.maximum(t, 1e-6)
        sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
        return jnp.where(t > 0, sampled, greedy)

    return jax.vmap(_row)(logits, seed, next_pos, temperature)


def sample_first(logits, spec: GenerateSpec, n_prompt: int) -> int:
    """First token from the prompt's logits: the prefill's (1, 1, V) of
    the last position, or the cold pipeline's (1, S, V) in-flight
    forward."""
    return int(sample_tokens(
        logits[:, -1, :],
        jnp.asarray([spec.seed], jnp.uint32),
        jnp.asarray([n_prompt], jnp.int32),
        jnp.asarray([spec.temperature], jnp.float32))[0])


def validate_spec(spec: GenerateSpec, n_prompt: int, cache_len: int) -> int:
    """Clamp n_new to the per-request max_len and validate against the
    KV cache capacity; returns the effective n_new.

    This replaces the old ``BatchedLMServer.generate`` behaviour of
    silently wrapping/dropping KV entries once S + n_new overran
    cache_len."""
    n_new = int(spec.n_new)
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {spec.n_new}")
    if spec.max_len is not None:
        n_new = min(n_new, int(spec.max_len) - n_prompt)
        if n_new < 1:
            raise CacheOverflowError(
                f"max_len={spec.max_len} leaves no room to generate "
                f"after a {n_prompt}-token prompt")
    if n_prompt + n_new > cache_len:
        raise CacheOverflowError(
            f"prompt ({n_prompt}) + n_new ({n_new}) = {n_prompt + n_new} "
            f"tokens overflow the decode cache (cache_len={cache_len}); "
            f"lower n_new / set max_len <= {cache_len} or provision a "
            f"larger cache")
    return n_new


def validate_spec_paged(spec: GenerateSpec, n_prompt: int, *,
                        page_tokens: int, n_pages: int,
                        stats: Optional["kvpages.KVPageStats"] = None) -> int:
    """Paged-mode admission check: the only *error* is a request that
    could never fit the page budget (everything smaller is blocking
    backpressure in the pool, not an exception).  Returns the effective
    n_new.  ``n_pages`` is the per-request page ceiling — min(pool
    budget, page-table width)."""
    n_new = int(spec.n_new)
    if n_new < 1:
        raise ValueError(f"n_new must be >= 1, got {spec.n_new}")
    if spec.max_len is not None:
        n_new = min(n_new, int(spec.max_len) - n_prompt)
        if n_new < 1:
            raise CacheOverflowError(
                f"max_len={spec.max_len} leaves no room to generate "
                f"after a {n_prompt}-token prompt")
    need = -(-(n_prompt + n_new) // page_tokens)
    if need > n_pages:
        occ = ""
        if stats is not None:
            occ = (f"; live occupancy {stats.used}/{stats.total} pages "
                   f"({stats.pinned} pinned, {stats.cached} cached)")
        raise CacheOverflowError(
            f"prompt ({n_prompt}) + n_new ({n_new}) needs {need} KV pages "
            f"but the per-request budget is {n_pages} pages x "
            f"{page_tokens} tokens = {n_pages * page_tokens} tokens{occ}; "
            f"lower n_new / set max_len or raise the page budget "
            f"(--kv-budget-mb)")
    return n_new


def paged_page_count(model, *, page_tokens: int,
                     budget_bytes: Optional[int] = None,
                     n_slots: int = 8, cache_len: int = 256) -> int:
    """Page budget for a scheduler: ``budget_bytes`` divided by the
    per-page device footprint across all paged layers, else (no byte
    budget, or a model with no paged layers — pure-SSM/ring states cost
    no page bytes) the slotted arena's worth of pages, so paged mode
    never regresses capacity by default."""
    per_page = model.kv_page_bytes(page_tokens)
    if budget_bytes and per_page > 0:
        n = int(budget_bytes) // per_page
        if n < 1:
            raise ValueError(
                f"kv budget {budget_bytes} B below one page "
                f"({per_page} B across paged layers)")
        return n
    return n_slots * (-(-cache_len // page_tokens))


def _as_prompt(prompt) -> jax.Array:
    arr = jnp.asarray(prompt, jnp.int32)
    if arr.ndim == 1:
        arr = arr[None, :]
    if arr.ndim != 2 or arr.shape[0] != 1 or arr.shape[1] < 1:
        raise ValueError(f"prompt must be (S,) or (1, S), got {arr.shape}")
    return arr


# ---------------------------------------------------------------------------
# results + per-request bookkeeping
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GenResult:
    """What one generation request produced."""
    tokens: List[int]            # emitted ids, first token included
    token_times: List[float]     # monotonic emission time per token
    n_prompt: int

    @property
    def t_first(self) -> float:
        return self.token_times[0]

    @property
    def tpot_s(self) -> List[float]:
        """Inter-token intervals (len == len(tokens) - 1)."""
        tt = self.token_times
        return [tt[i] - tt[i - 1] for i in range(1, len(tt))]


class _Active:
    """One resident request (pending join or holding a slot)."""

    def __init__(self, spec: GenerateSpec, cache1: PyTree, first: int,
                 t_first: float, n_prompt: int, n_new: int):
        self.spec = spec
        self.cache1 = cache1            # B=1 prefilled cache, until joined
        self.tokens = [first]
        self.times = [t_first]
        self.n_prompt = n_prompt
        self.remaining = n_new - 1
        self.done = False
        self.error: Optional[BaseException] = None
        # paged mode only: reserved physical pages (prefix hits first),
        # how many of them were prefix hits, and the prompt's running
        # page hashes (for publishing after the pack)
        self.page_ids: List[int] = []
        self.n_hit = 0
        self.hashes: List[str] = []

    @property
    def next_pos(self) -> int:
        """Absolute position of the next input token (the last emitted
        one): prompt occupies [0, S), generated token i sits at S + i."""
        return self.n_prompt + len(self.tokens) - 1


@functools.lru_cache(maxsize=16)
def _prefill_fn(model, fingerprint):
    """Jitted model.prefill per (model, kernel-dispatch fingerprint).

    The lambda matters: jax's global pjit cache keys on the underlying
    *function* — ``jax.jit(model.prefill)`` from two schedulers shares
    one trace, so a scheduler built after a ``REPRO_PALLAS`` change
    would silently reuse executables that baked the previous kernels
    in.  A fresh closure per cache entry gives each (model, modes) pair
    its own trace while still sharing it across schedulers of the same
    model (scale-out).  The empty cache is donated: the filled one
    takes its place, so a queued prefill holds one."""
    return jax.jit(lambda params, batch, cache:
                   model.prefill(params, batch, cache), donate_argnums=2)


@functools.lru_cache(maxsize=16)
def _step_fn(model, fingerprint):
    """Jitted batched decode step + sampling, shared across every
    scheduler of the same (model, dispatch) — same caching rationale as
    :func:`_prefill_fn`.  Sharing matters for serving: schedulers are
    rebuilt on every cold start and prewarm, and a per-scheduler
    ``jax.jit`` closure both recompiled the step on each fresh
    instance's first generation (~seconds of on-path latency that no
    amount of pre-provisioning could hide) and leaked one pinned
    executable per instance lifetime into the global pjit cache."""
    def step(params, cache, tok, pos, seed, temp):
        logits, cache = model.decode_step(params, cache, tok, pos)
        nxt = sample_tokens(logits[:, -1, :], seed, pos + 1, temp)
        return nxt[:, None], cache
    return jax.jit(step)


@functools.lru_cache(maxsize=16)
def _join_fn(model, fingerprint):
    """Jitted slot-merge (B=1 prefilled cache -> batch row ``slot``),
    shared like :func:`_step_fn`.  Top-level keys distinguish the
    stacked pattern groups ('s*': leaves are (n_units, B, ...)) from
    tail layers ('t*': leaves are (B, ...))."""
    def join(cache, one, slot):
        out = {}
        for k, big in cache.items():
            ax = 1 if k.startswith("s") else 0
            out[k] = jax.tree.map(
                lambda b, s, _ax=ax: jax.lax.dynamic_update_slice_in_dim(
                    b, s.astype(b.dtype), slot, axis=_ax), big, one[k])
        return out
    return jax.jit(join)


# paged-mode twins of the factories above — same caching and fresh-closure
# rationale (never jit a bound method: R5)

@functools.lru_cache(maxsize=16)
def _paged_step_fn(model, fingerprint):
    """The page pools are donated: each layer writes its rows into them
    in place, so a step holds one pool, not an input and an output.
    The scheduler dispatches the step under its lock and hands the
    result pools on at once, so no other thread sees a donated pool."""
    def step(params, cache, pools, tables, tok, pos, seed, temp):
        logits, cache, pools = model.decode_step_paged(
            params, cache, pools, tables, tok, pos)
        nxt = sample_tokens(logits[:, -1, :], seed, pos + 1, temp)
        return nxt[:, None], cache, pools
    return jax.jit(step, donate_argnums=2)


@functools.lru_cache(maxsize=16)
def _prefill_cont_fn(model, fingerprint):
    return jax.jit(
        lambda params, batch, cache, off:
        model.prefill_continue(params, batch, cache, off=off),
        static_argnums=(3,))


@functools.lru_cache(maxsize=16)
def _gather_fn(model, fingerprint):
    return jax.jit(
        lambda cache, pools, ids: model.gather_pages(cache, pools, ids))


@functools.lru_cache(maxsize=16)
def _pack_fn(model, fingerprint):
    """The pool is donated: the joins of one step boundary pack in turn
    into one pool, where each would otherwise allocate a new one while
    its input waits in the device queue."""
    return jax.jit(
        lambda pools, cache, ids, first:
        model.pack_pages(pools, cache, ids, first),
        static_argnums=(3,), donate_argnums=0)


class DecodeScheduler:
    """Continuous-batching decode over one slotted KV cache.

    Thread-safe: any number of threads may call :meth:`generate`
    concurrently; their requests share the batched step.  ``n_slots``
    bounds concurrent residency (the honored successor of the old
    server's dead ``max_batch`` knob) — an (n_slots+1)-th caller blocks
    until a slot frees, which continuous batching makes soon and often.

    The jitted prefill and decode step trace the model's attention
    through the kernel registry (:mod:`repro.kernels.ops`): on a TPU
    backend serving runs the ``flash_attention`` / ``decode_attention``
    Pallas kernels the tests verify; elsewhere the ``ref`` fallback (or
    the ``REPRO_PALLAS``/``--pallas`` forced mode) is baked in at trace
    time — :attr:`kernel_modes` records the resolution this scheduler
    was built under.
    """

    def __init__(self, model, params: PyTree, *, n_slots: int = 8,
                 cache_len: int = 256,
                 kv_page_tokens: Optional[int] = None,
                 kv_budget_bytes: Optional[int] = None,
                 kv_max_seq: Optional[int] = None,
                 metrics: Optional[metrics_mod.MetricsRegistry] = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if cache_len < 2:
            raise ValueError(f"cache_len must be >= 2, got {cache_len}")
        self.model = model
        self.params = params
        self.n_slots = int(n_slots)
        self.cache_len = int(cache_len)
        # paged mode: full-attention KV lives in a shared page pool
        # (kvpages.KVPagePool bookkeeping + init_kv_pages device arrays)
        # instead of per-slot arena rows; admission is page-budgeted
        self.paged = kv_page_tokens is not None
        m = metrics_mod.resolve(metrics)
        if self.paged:
            pt = int(kv_page_tokens)
            if pt < 1:
                raise ValueError(
                    f"kv_page_tokens must be >= 1, got {kv_page_tokens}")
            self.page_tokens = pt
            self.n_pages = paged_page_count(
                model, page_tokens=pt, budget_bytes=kv_budget_bytes,
                n_slots=self.n_slots, cache_len=self.cache_len)
            # page-table width == per-request page ceiling; it bounds
            # the logical attention extent NP*pt — and with it
            # fallback-mode gather traffic — so it defaults to the
            # slotted cache_len rather than the whole pool.  Pass
            # kv_max_seq > cache_len to let one request stretch across
            # more of the page budget than a slotted arena row held.
            self.np_max = max(1, min(
                self.n_pages,
                -(-int(kv_max_seq if kv_max_seq is not None
                       else self.cache_len) // pt)))
            self.kvpool = kvpages.KVPagePool(
                n_pages=self.n_pages, page_tokens=pt,
                page_bytes=model.kv_page_bytes(pt),
                model_key=model.cfg.name, metrics=m)
            # device pools carry one trailing scratch page that inactive
            # batch rows write into
            self._kvpages = model.init_kv_pages(                # guarded-by: _cv
                self.n_pages + 1, pt)
            self._cache = model.init_cache_paged(               # guarded-by: _cv
                self.n_slots, self.cache_len)
            self._tables = np.full((self.n_slots, self.np_max),  # guarded-by: _cv
                                   self.kvpool.scratch_id, np.int32)
            # prefix reuse needs every sequence state paged; a model with
            # any slot-resident kind still pages admission accounting but
            # keeps the slotted length ceiling (ring/SSM semantics)
            self._prefix_ok = model.supports_prefix_cache
            self._all_paged = bool(model.paged_kinds()) and all(
                k in model.paged_kinds()
                for k in set(model.pattern) | set(model.tail_kinds))
        else:
            self._cache = model.init_cache(self.n_slots, self.cache_len)  # guarded-by: _cv
        # host-side per-slot step inputs
        self._tok = np.zeros((self.n_slots, 1), np.int32)    # guarded-by: _cv
        self._pos = np.zeros((self.n_slots,), np.int32)      # guarded-by: _cv
        self._seed = np.zeros((self.n_slots,), np.uint32)    # guarded-by: _cv
        self._temp = np.zeros((self.n_slots,), np.float32)   # guarded-by: _cv
        self._cv = analysis.make_condition("DecodeScheduler._cv")
        self._free: List[int] = list(range(self.n_slots))  # guarded-by: _cv
        self._slots: Dict[int, _Active] = {}               # guarded-by: _cv
        self._pending: deque = deque()                     # guarded-by: _cv
        self._stepping = False                             # guarded-by: _cv
        # the dispatch fingerprint this scheduler's jitted prefill/step
        # bake in
        self._fingerprint = ops.registry.fingerprint()
        # shared per (model, registry resolution) — a fresh scheduler
        # (cold start, prewarm) reuses the already-compiled executables
        # (never a bound method: those share jax's global cache by
        # (__func__, __self__) equality — R5)
        self._prefill = _prefill_fn(model, self._fingerprint)
        self._step = _step_fn(model, self._fingerprint)
        self._join_cache = _join_fn(model, self._fingerprint)
        if self.paged:
            self._pstep = _paged_step_fn(model, self._fingerprint)
            self._prefill_cont = _prefill_cont_fn(model, self._fingerprint)
            self._gather = _gather_fn(model, self._fingerprint)
            self._pack = _pack_fn(model, self._fingerprint)
        # counters
        self.steps = 0
        self.max_occupancy = 0
        self.joined = 0
        # shared across all schedulers of a platform: occupancy/steps
        # aggregate over instances (the decode capacity the node runs)
        self._m_steps = m.counter("decode/steps")
        # pages the paged kernel fetches per layer: its work follows pos
        self._m_pages = m.counter("decode/kv_pages_attended")
        self._m_joined = m.counter("decode/joined")
        self._m_occ = m.gauge("decode/occupancy")

    # ------------------------------------------------------------ public API
    def generate(self, spec: GenerateSpec, *,
                 first_token: Optional[int] = None,
                 t_first: Optional[float] = None) -> GenResult:
        """Serve one generation request; blocks until it completes.

        ``first_token``/``t_first`` inject a token already produced
        elsewhere — the cold-start path, where the loading pipeline's
        own in-flight forward answers the prompt (TTFT ~ the pipeline's
        E-completion): the prompt is still prefilled here to build the
        slot's KV cache, but its logits are discarded and generation
        resumes at position S+1.
        """
        prompt = _as_prompt(spec.prompt)
        n_prompt = int(prompt.shape[1])
        if self.paged:
            return self._generate_paged(spec, prompt, n_prompt,
                                        first_token, t_first)
        n_new = validate_spec(spec, n_prompt, self.cache_len)

        with _span("decode.first_token"), _span("decode.prefill"):
            cache1 = self.model.init_cache(1, self.cache_len)
            logits, cache1 = self._prefill(self.params, {"tokens": prompt},
                                           cache1)
            if first_token is None:
                jax.block_until_ready(logits)
                first_token = sample_first(logits, spec, n_prompt)
                t_first = time.monotonic()

        req = _Active(spec, cache1, int(first_token), float(t_first),
                      n_prompt, n_new)
        if req.remaining == 0 or (spec.eos_id is not None
                                  and req.tokens[-1] == spec.eos_id):
            return GenResult(req.tokens, req.times, n_prompt)

        with self._cv:
            self._pending.append(req)
            self._cv.notify_all()
        self._pump(req)
        if req.error is not None:
            raise req.error
        return GenResult(req.tokens, req.times, n_prompt)

    def _generate_paged(self, spec: GenerateSpec, prompt, n_prompt: int,
                        first_token, t_first) -> GenResult:
        """Paged admission: reserve whole pages (prefix hits first, the
        rest all-or-nothing from the pool — blocking backpressure, never
        a per-slot length ceiling), prefill only the unshared suffix,
        then join the batch like any slotted request."""
        pt = self.page_tokens
        n_new = validate_spec_paged(spec, n_prompt, page_tokens=pt,
                                    n_pages=self.np_max,
                                    stats=self.kvpool.stats())
        if not self._all_paged:
            # some sequence state is still slot-resident (ring / SSM):
            # its capacity ceiling applies unchanged
            n_new = validate_spec(spec, n_prompt, self.cache_len)
        need = -(-(n_prompt + n_new) // pt)
        with _span("decode.first_token"):
            hit: List[int] = []
            if self._prefix_ok:
                hashes = kvpages.page_hashes(self.kvpool.model_key,
                                             np.asarray(prompt)[0], pt)
                # a hit must leave a non-empty prefill suffix (the
                # request's own logits come from its last prompt token)
                hashes_full = hashes
                hashes = hashes[:min(len(hashes), (n_prompt - 1) // pt)]
                hit = self.kvpool.match_prefix(hashes)
            else:
                hashes_full = []
            with _span("decode.kv_alloc"):
                try:
                    new = self.kvpool.alloc(need - len(hit), timeout=120.0)
                except TimeoutError:
                    # our own prefix pins may be what is starving the
                    # pool: drop them and queue for the whole span like
                    # a cold request
                    self.kvpool.release(hit)
                    hit = []
                    new = self.kvpool.alloc(need)
            page_ids = list(hit) + list(new)
            n_hit = len(hit)
            try:
                with _span("decode.prefill"):
                    cache1 = self.model.init_request_cache(need * pt,
                                                           self.cache_len)
                    off = n_hit * pt
                    if off:
                        # hit pages are pinned, so immutable; dispatched
                        # under the lock, before any later pack or step
                        # donates the pool array it reads
                        with self._cv:
                            cache1 = self._gather(
                                cache1, self._kvpages,
                                jnp.asarray(np.asarray(hit, np.int32)))
                        logits, cache1 = self._prefill_cont(
                            self.params, {"tokens": prompt[:, off:]},
                            cache1, off)
                    else:
                        logits, cache1 = self._prefill(
                            self.params, {"tokens": prompt}, cache1)
                    if first_token is None:
                        jax.block_until_ready(logits)
                        first_token = sample_first(logits, spec, n_prompt)
                        t_first = time.monotonic()
                req = _Active(spec, cache1, int(first_token),
                              float(t_first), n_prompt, n_new)
                req.page_ids = page_ids
                req.n_hit = n_hit
                req.hashes = hashes_full
            except BaseException:
                self.kvpool.release(page_ids)
                raise
        if req.remaining == 0 or (spec.eos_id is not None
                                  and req.tokens[-1] == spec.eos_id):
            self.kvpool.release(page_ids)
            return GenResult(req.tokens, req.times, n_prompt)

        with self._cv:
            self._pending.append(req)
            self._cv.notify_all()
        self._pump(req)
        if req.error is not None:
            raise req.error
        return GenResult(req.tokens, req.times, n_prompt)

    @property
    def kernel_modes(self) -> Dict[str, str]:
        """Resolved kernel-registry dispatch per op as of this
        scheduler's construction (what its jitted prefill/step bake in
        — set the mode BEFORE building schedulers); exact even after a
        later ``set_mode``."""
        return ops.registry.modes_for(self._fingerprint)

    def stats(self) -> Dict[str, int]:
        with self._cv:
            out = {"steps": self.steps, "joined": self.joined,
                   "max_occupancy": self.max_occupancy,
                   "active": len(self._slots) + len(self._pending),
                   "n_slots": self.n_slots}
        if self.paged:
            ps = self.kvpool.stats()
            out.update(kv_page_tokens=self.page_tokens,
                       kv_pages_total=ps.total, kv_pages_used=ps.used,
                       kv_pages_pinned=ps.pinned,
                       kv_prefix_hits=ps.prefix_hits,
                       kv_prefix_misses=ps.prefix_misses)
        return out

    def reset_peaks(self):
        """Re-arm the max_occupancy watermark at the current occupancy
        — benchmark sweeps call this between phases so each phase
        reports its own peak, not the scheduler-lifetime maximum."""
        with self._cv:
            self.max_occupancy = len(self._slots)

    # -------------------------------------------------------- cooperative drive
    def _admit_locked(self):
        """Move pending joins into free slots (caller holds the lock) —
        the step boundary where requests enter the running batch."""
        while self._pending and self._free:
            req = self._pending.popleft()
            slot = min(self._free)
            self._free.remove(slot)
            if self.paged:
                self._join_paged_locked(req, slot)
            else:
                self._cache = self._join_cache(self._cache, req.cache1,
                                               jnp.int32(slot))
            req.cache1 = None
            self._slots[slot] = req
            self._tok[slot, 0] = req.tokens[-1]
            self._pos[slot] = req.next_pos
            self._seed[slot] = np.uint32(req.spec.seed)
            self._temp[slot] = np.float32(req.spec.temperature)
            self.joined += 1
            self.max_occupancy = max(self.max_occupancy, len(self._slots))
            self._m_joined.inc()
            self._m_occ.set(len(self._slots))

    def _join_paged_locked(self, req: _Active, slot: int):
        """Paged half of admission (caller holds the lock): merge the
        slot-resident state, move new prompt pages from the request's
        contiguous prefill cache into the pool, publish their hashes for
        prefix reuse, and point the slot's page-table row at them."""
        self._cache = self._join_cache(
            self._cache, self.model.strip_paged(req.cache1), jnp.int32(slot))
        n_pp = -(-req.n_prompt // self.page_tokens)   # pages holding prompt
        ids = req.page_ids
        # copy-on-write guard on the pack targets — fresh allocations
        # have refcount 1, so this only ever forks if a future caller
        # grows sharing semantics; the invariant stays locally enforced
        for j in range(req.n_hit, n_pp):
            pid, copied = self.kvpool.ensure_writable(ids[j])
            if copied:
                self._kvpages = self.model.copy_page(self._kvpages,
                                                     ids[j], pid)
                ids[j] = pid
        if n_pp > req.n_hit:
            self._kvpages = self._pack(
                self._kvpages, req.cache1,
                jnp.asarray(np.asarray(ids[req.n_hit:n_pp], np.int32)),
                req.n_hit)
        # publish *full* prompt pages only (device content final now);
        # partial trailing pages keep receiving decode writes
        for j in range(req.n_hit, min(len(req.hashes), n_pp)):
            self.kvpool.register(ids[j], req.hashes[j])
        self._tables[slot, :] = self.kvpool.scratch_id
        self._tables[slot, :len(ids)] = ids

    def _leave_paged_locked(self, req: _Active, slot: int):
        """Release a leaver's page references and park its table row on
        the scratch page at position 0, so the dead row costs the
        kernel one page (caller holds the lock)."""
        self._tables[slot, :] = self.kvpool.scratch_id
        self._pos[slot] = 0
        self.kvpool.release(req.page_ids)
        req.page_ids = []

    def _fail_locked(self, e: BaseException):
        """Abort every resident request with ``e`` (caller holds the
        lock): a failed step/join leaves no thread parked forever."""
        self._stepping = False
        for req in list(self._slots.values()) + list(self._pending):
            req.error = e
            if self.paged and req.page_ids:
                self.kvpool.release(req.page_ids)
                req.page_ids = []
        if self.paged:
            self._tables[:, :] = self.kvpool.scratch_id
            self._pos[:] = 0
        self._slots.clear()
        self._pending.clear()
        self._free = list(range(self.n_slots))
        self._cv.notify_all()

    def _pump(self, my: _Active):
        """Drive batched steps until ``my`` completes.  Exactly one
        thread steps at a time; the others wait on the CV.  Every
        resident request has a caller thread parked here, so a stepper
        always exists while work remains.  A step's ``decode.step``
        span runs from taking ``_stepping`` to releasing it."""
        while True:
            with contextlib.ExitStack() as step:
                with self._cv:
                    while True:
                        if my.done or my.error is not None:
                            return
                        if not self._stepping:
                            break
                        self._cv.wait()
                    self._stepping = True
                    step.enter_context(_span("decode.step"))
                    try:
                        with _span("decode.admit"):
                            self._admit_locked()
                        params, cache = self.params, self._cache
                        tok = jnp.asarray(self._tok)
                        pos = jnp.asarray(self._pos)
                        seed = jnp.asarray(self._seed)
                        temp = jnp.asarray(self._temp)
                        if self.paged:
                            pages = int((self._pos // self.page_tokens
                                         + 1).sum())
                            # dispatched under the lock: the step
                            # consumes the pools, and a prefix hit's
                            # gather must read the step's output, never
                            # the donated input
                            nxt, new_cache, self._kvpages = self._pstep(
                                params, cache, self._kvpages,
                                jnp.asarray(self._tables), tok, pos, seed,
                                temp)
                    except BaseException as e:
                        # anything failing while _stepping is set must
                        # fail ALL residents, or their threads wait
                        # forever
                        self._fail_locked(e)
                        raise
                try:
                    if not self.paged:
                        nxt, new_cache = self._step(params, cache, tok,
                                                    pos, seed, temp)
                    nxt_host = np.asarray(nxt)
                except BaseException as e:
                    with self._cv:
                        self._fail_locked(e)
                    raise
                t_now = time.monotonic()
                with self._cv:
                    self._cache = new_cache
                    self.steps += 1
                    for slot in list(self._slots):
                        req = self._slots[slot]
                        t = int(nxt_host[slot, 0])
                        req.tokens.append(t)
                        req.times.append(t_now)
                        req.remaining -= 1
                        self._tok[slot, 0] = t
                        self._pos[slot] += 1
                        if req.remaining == 0 or \
                                (req.spec.eos_id is not None
                                 and t == req.spec.eos_id):
                            req.done = True
                            del self._slots[slot]
                            self._free.append(slot)
                            if self.paged:
                                self._leave_paged_locked(req, slot)
                    self._m_steps.inc()
                    if self.paged:
                        self._m_pages.inc(pages)
                    self._m_occ.set(len(self._slots))
                    self._stepping = False
                    self._cv.notify_all()


# ---------------------------------------------------------------------------
# serial reference — the oracle the batched engine must match bit-for-bit
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _ref_fns(model, fingerprint):
    """Per-(model, kernel-dispatch) jitted prefill/decode_step, cached
    so repeated reference calls (the bench's serial baseline) don't
    recompile.  Keyed on the registry fingerprint — and wrapped in
    per-entry closures, since the global pjit cache keys on the
    underlying function: ``jax.jit(model.prefill)`` would reuse a
    trace from a previous dispatch mode.  Bounded: the jitted closures
    pin the model and its executables, so an unbounded cache would
    leak one model per entry for the process lifetime."""
    return (jax.jit(lambda p, b, c: model.prefill(p, b, c)),
            jax.jit(lambda p, c, t, s: model.decode_step(p, c, t, s)))


def reference_generate(model, params: PyTree, prompt, *, n_new: int,
                       cache_len: int = 256, temperature: float = 0.0,
                       seed: int = 0, eos_id: Optional[int] = None,
                       max_len: Optional[int] = None) -> List[int]:
    """Serial B=1 ``prefill`` + ``decode_step`` loop with the same
    sampling rule as the DecodeScheduler.  Token-level ground truth for
    the equivalence tests and the bench's per-request serial baseline.
    """
    spec = GenerateSpec(prompt=prompt, n_new=n_new, temperature=temperature,
                        max_len=max_len, eos_id=eos_id, seed=seed)
    prompt = _as_prompt(prompt)
    S = int(prompt.shape[1])
    n_new = validate_spec(spec, S, cache_len)

    prefill, dec = _ref_fns(model, ops.registry.fingerprint())
    cache = model.init_cache(1, cache_len)
    logits, cache = prefill(params, {"tokens": prompt}, cache)
    out = [sample_first(logits, spec, S)]
    seeds = jnp.asarray([seed], jnp.uint32)
    temps = jnp.asarray([temperature], jnp.float32)
    cur = jnp.asarray([[out[0]]], jnp.int32)
    for t in range(S, S + n_new - 1):
        if eos_id is not None and out[-1] == eos_id:
            break
        pos = jnp.asarray([t], jnp.int32)
        logits, cache = dec(params, cache, cur, pos)
        cur = sample_tokens(logits[:, -1, :], seeds, pos + 1, temps)[:, None]
        out.append(int(cur[0, 0]))
    return out
