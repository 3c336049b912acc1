#!/usr/bin/env python3
"""Chip smoke: the serving main path, end to end, on a TPU.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # the sharded cold start

One chip: deploys full-width smollm-360m (random weights made from
``--seed``) to a weight store inside the checkout and serves it through
ServerlessPlatform -> Router -> InstancePool -> ColdStartEngine ->
DecodeScheduler, once with the slotted KV cache and once with 16-token
KV pages.  It checks:

  * the logits of a one-shot cold request, computed inside the loading
    pipeline, against a plain float32 forward of the same parameters
    (relative error within :func:`logit_tol`);
  * a cold generation request whose first token comes out of the
    loading pipeline, then warm requests that join the continuous
    batch (300-token prompts, a 96-token shared prefix, a short
    prompt).  Each token stream is replayed, teacher-forced, through
    ``reference_generate``'s serial batch-1 computation: every served
    token must be the reference's choice or a near-tie within
    :func:`token_gap_tol` of it.  Whether the streams are identical to
    ``reference_generate`` is printed: on a TPU the batch-4 decode step
    rounds differently from the batch-1 one, so near-ties can flip;
  * the same request at a different batch occupancy, and a decode
    step at batch 4 with its other rows empty or busy: bit-identical;
  * every main-path kernel resolved to ``pallas``.

``--chips 4`` runs only the sharded cold start: full-width smollm-360m
on a (1, 4) mesh against the single-device cold start in the same
process.  One-shot cold logits must be bit-identical, every device must
hold its share of the parameters, warm tensor-parallel decode logits
(teacher-forced on the single-device tokens) must stay within
:func:`logit_tol` of single-device decode, and the attention kernels
must run as ``pallas`` on the mesh too.

Times printed here are smoke output, not benchmark numbers.  The last
line is ``{"ok": true, "device": {...}}``; without a TPU, or when any
check fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import itertools
import json
import os
import shutil
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.api import get_config  # noqa: E402
from repro.serving.api import GenerateSpec, Request  # noqa: E402
from repro.serving.decode import reference_generate  # noqa: E402
from repro.serving.engine import ServerlessPlatform  # noqa: E402
from repro.store.store import WeightStore, deploy_model  # noqa: E402

MODEL = "smollm-360m"
STORE_DIR = os.path.join(ROOT, ".chip_smoke_store")
PAGE_TOKENS = 16
CACHE_LEN = 512
N_NEW = 32
PROMPT_LEN = 300          # not a multiple of any kernel tile
PREFIX_LEN = 96           # six 16-token pages shared by two prompts


def logit_tol(n_layers: int) -> float:
    """Limit on the relative RMS error of bf16 serving logits against
    float32.  The residual stream is rounded to bf16 after every layer,
    so the error grows as the square root of depth: at smollm-360m
    widths it measured 0.020 / 0.030 / 0.045 at 2 / 4 / 8 layers (CPU),
    so the limit is 0.025 * sqrt(layers), 0.14 at 32.  A wrong formula
    (rotation, head mapping, norm) gives an error of order one.  Two
    bf16 evaluations that round in a different order (tensor-parallel
    against one device) are held to the same limit."""
    return 0.025 * n_layers ** 0.5

MAIN_PATH_KERNELS = ("flash_attention", "decode_attention",
                     "decode_attention_paged", "kv_pages_write")


# ---------------------------------------------------------------------------
# float32 reference forward
# ---------------------------------------------------------------------------

HI = jax.lax.Precision.HIGHEST


def reference_logits(cfg, params, tokens):
    """Plain float32 forward of a dense decoder, written out in
    ``jax.numpy`` apart from ``repro.models``: RMSNorm scaled by
    ``1 + scale``, rotary embedding over split halves, causal GQA
    softmax attention, SwiGLU MLP, untied head.  tokens: (B, S)."""
    f32 = jnp.float32
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    S = tokens.shape[1]

    def norm(x, scale):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * (1.0 + scale.astype(f32))

    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, dh, 2, dtype=f32) / dh)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):                                   # (B, S, heads, dh)
        a, b = t[..., :dh // 2], t[..., dh // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def mm(eq, a, b):
        return jnp.einsum(eq, a, b.astype(f32), precision=HI)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = norm(x, p["norm1"]["scale"])
        q = rope(mm("bsd,dhk->bshk", h, p["attn"]["wq"]))
        k = rope(mm("bsd,dhk->bshk", h, p["attn"]["wk"]))
        v = mm("bsd,dhk->bshk", h, p["attn"]["wv"])
        k = jnp.repeat(k, H // K, axis=2)          # q head j reads kv j // rep
        v = jnp.repeat(v, H // K, axis=2)
        s = jnp.einsum("bqhk,bthk->bhqt", q, k, precision=HI) / np.sqrt(dh)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqt,bthk->bqhk", a, v, precision=HI)
        x = x + mm("bshk,hkd->bsd", o, p["attn"]["wo"])
        h = norm(x, p["norm2"]["scale"])
        g = jax.nn.silu(mm("bsd,df->bsf", h, p["mlp"]["wg"]))
        u = mm("bsd,df->bsf", h, p["mlp"]["wu"])
        return x + mm("bsf,fd->bsd", g * u, p["mlp"]["wd"]), None

    x = params["embed"]["tok"].astype(f32)[tokens]
    x, _ = jax.lax.scan(layer, x, params["blocks"]["s0"])
    x = norm(x, params["final"]["norm"]["scale"])
    return mm("bsd,dv->bsv", x, params["final"]["head"]["w"])


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# bookkeeping
# ---------------------------------------------------------------------------

class Checks:
    """Named pass/fail lines; the smoke fails if any check failed."""

    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = ""):
        print(f"[{'PASS' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name: str, fn, *args):
        """Run one phase; an exception fails it (and the smoke) but the
        later phases still run, so one chip run shows every fault."""
        print(f"--- {name}", flush=True)
        t0 = time.monotonic()
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc()
            self.check(f"{name} ran to its end", False)
        print(f"--- {name}: {time.monotonic() - t0:.1f}s", flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def line(self) -> str:
        with self._lock:
            return (f"compile: {self.seconds:.1f}s in backend compiles, "
                    f"persistent cache hits={self.hits} "
                    f"misses={self.misses}")


class Smoke:
    """One deployed model, its prompts and the platforms serving it."""

    def __init__(self, cfg, seed: int, *, store_dir: str = STORE_DIR,
                 prompt_len: int = PROMPT_LEN, prefix_len: int = PREFIX_LEN,
                 cache_len: int = CACHE_LEN, page_tokens: int = PAGE_TOKENS,
                 n_new: int = N_NEW):
        self.cfg = cfg
        self.store_dir = store_dir
        self.model = transformer.build(cfg)
        self.cache_len, self.page_tokens, self.n_new = \
            cache_len, page_tokens, n_new
        r = np.random.default_rng(seed)

        def toks(n):
            return r.integers(0, cfg.vocab_size, (n,)).astype(np.int32)

        prefix = toks(prefix_len)
        self.p_cold = toks(prompt_len)
        self.wave1 = [np.concatenate([prefix, toks(prompt_len - prefix_len)]),
                      toks(prompt_len // 7)]
        # second wave: the prefix again, and the cold prompt again — both
        # page-aligned prefix hits under paged KV
        self.wave2 = [np.concatenate([prefix, toks(prompt_len // 2)]),
                      self.p_cold]
        t0 = time.monotonic()
        shutil.rmtree(store_dir, ignore_errors=True)
        self.store = WeightStore(store_dir)
        deploy_model(self.store, self.model, cfg.name, jax.random.key(seed))
        print(f"deploy: {cfg.name} {cfg.param_count() / 1e6:.1f}M params "
              f"({self.store.model_nbytes(cfg.name) / 1e9:.2f} GB) in "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        self._ids = itertools.count()
        self.identical = {}        # label -> (streams identical, streams)
        # one pair of jits for every teacher-forced replay, so a replay
        # compiles once per prompt length, as reference_generate does
        m = self.model
        self._prefill = jax.jit(lambda p, b, cc: m.prefill(p, b, cc))
        self._step = jax.jit(lambda p, cc, t, q: m.decode_step(p, cc, t, q))

    def forced_logits(self, params, prompt, tokens, rows: int = 1,
                      fill_rows: bool = False):
        """Teacher-forced serial replay of ``reference_generate``'s
        computation: B=1 prefill of ``prompt``, then decode steps fed
        ``tokens[:-1]``.  Returns the next-token logits before each of
        ``tokens``, (n, V) float32.

        ``rows > 1`` runs the decode steps at batch ``rows`` with the
        request in row 0, as the scheduler's slotted arena does; the
        other rows are empty, or (``fill_rows``) hold the same request
        at the same position."""
        S = len(prompt)
        c1 = self.model.init_cache(1, self.cache_len)
        lg, c1 = self._prefill(params, {"tokens": jnp.asarray(prompt[None])},
                               c1)
        out = [lg[0, -1]]
        cache = c1 if rows == 1 else jax.tree.map(
            lambda one, arena: _put_row(one, arena, fill_rows), c1,
            self.model.init_cache(rows, self.cache_len))
        for i, t in enumerate(tokens[:-1]):
            tok = jnp.full((rows, 1), t if fill_rows else 0, jnp.int32)
            pos = jnp.full((rows,), S + i if fill_rows else 0, jnp.int32)
            lg, cache = self._step(params, cache, tok.at[0, 0].set(t),
                                   pos.at[0].set(S + i))
            out.append(lg[0, -1])
        return np.asarray(jnp.stack(out), np.float32)

    def platform(self, **kw) -> ServerlessPlatform:
        example = {"tokens": jnp.asarray(self.p_cold[None])}
        return ServerlessPlatform(
            self.store, {self.cfg.name: lambda: (self.model, example)},
            strategy="cicada", keep_alive_s=60.0, max_instances=1,
            gen_slots=4, gen_cache_len=self.cache_len, **kw)

    def submit(self, router, **kw):
        return router.submit(Request(req_id=next(self._ids),
                                     model=self.cfg.name, **kw))

    def spec(self, prompt, seed=None) -> GenerateSpec:
        """Greedy, or sampled at temperature 0.7 under ``seed``."""
        return GenerateSpec(prompt=prompt, n_new=self.n_new,
                            temperature=0.0 if seed is None else 0.7,
                            seed=seed or 0)

    def generate(self, router, specs):
        futs = [self.submit(router, gen=sp) for sp in specs]
        return [f.result(timeout=900) for f in futs]

    def reference(self, params, spec: GenerateSpec):
        return reference_generate(self.model, params, spec.prompt,
                                  n_new=spec.n_new, cache_len=self.cache_len,
                                  temperature=spec.temperature,
                                  seed=spec.seed)

    def cleanup(self):
        shutil.rmtree(self.store_dir, ignore_errors=True)


def _put_row(one, arena, fill: bool):
    """Place a B=1 cache leaf into row 0 of a batch-``n`` arena leaf
    (the batch axis is the one where the shapes differ); ``fill``
    copies it into every row."""
    axis = next(i for i, (a, b) in enumerate(zip(one.shape, arena.shape))
                if a != b)
    if fill:
        return jnp.repeat(one, arena.shape[axis], axis=axis)
    return jax.lax.dynamic_update_slice_in_dim(arena, one, 0, axis)


def sampling_scores(logits, spec: GenerateSpec, next_pos: int):
    """What ``sample_tokens`` takes the argmax of, for one row: the
    logits when greedy; logits / temperature plus the Gumbel noise
    ``jax.random.categorical`` adds under the request's key when
    sampled.  (V,) float32."""
    lg = jnp.asarray(logits, jnp.float32)
    if spec.temperature == 0:
        return lg
    key = jax.random.fold_in(jax.random.PRNGKey(spec.seed), next_pos)
    return lg / spec.temperature + jax.random.gumbel(key, lg.shape,
                                                     jnp.float32)


def score_gaps(spec: GenerateSpec, tokens, logits) -> np.ndarray:
    """For each served token: how far its sampling score falls below
    the best score under the reference's teacher-forced logits (0 where
    the reference picks the served token itself).  Logit units."""
    S = len(spec.prompt)
    gaps = []
    for i, t in enumerate(tokens):
        sc = np.asarray(sampling_scores(logits[i], spec, S + i))
        gaps.append(float(sc.max() - sc[t]) * (spec.temperature or 1.0))
    return np.asarray(gaps)


def _instance(plat, name):
    return plat.pools[name]._instances[0]


def _first_index(a, b) -> int:
    return next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def _first_diff(a, b) -> str:
    return (f"first difference at token {_first_index(a, b)}: "
            f"{[int(x) for x in a]} vs {[int(x) for x in b]}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def check_oneshot_cold(c: Checks, s: Smoke, plat, router):
    resp = s.submit(router, batch={"tokens": jnp.asarray(s.p_cold[None])}
                    ).result(timeout=900)
    params = _instance(plat, s.cfg.name).params
    ref = jax.jit(reference_logits, static_argnums=0)(
        s.cfg, params, jnp.asarray(s.p_cold[None]))
    got = np.asarray(resp.logits, np.float32)
    want = np.asarray(ref)
    err = rel_err(got, want)
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    c.check("one-shot cold request served by the pipeline", resp.cold,
            f"load {resp.load_s:.3f}s, pipeline utilization "
            f"{resp.utilization:.2f}")
    c.check("cold logits finite, shape (1, S, V)",
            got.shape == (1, len(s.p_cold), s.cfg.vocab_size)
            and bool(np.isfinite(got).all()), str(got.shape))
    tol = logit_tol(s.cfg.n_layers)
    c.check("cold logits vs float32 reference", err <= tol,
            f"relative RMS error {err:.3e} (tol {tol:.3f}), "
            f"max |diff| {np.abs(got - want).max():.3e} "
            f"of max |ref| {np.abs(want).max():.3e}, "
            f"top-1 agreement {top1:.3f}")


def check_generation(c: Checks, s: Smoke, plat, router, label: str):
    """Cold generation (first token inside the pipeline), then two
    waves of concurrent warm requests joining the continuous batch."""
    cold_spec = s.spec(s.p_cold)
    cold = s.generate(router, [cold_spec])[0]
    inst = _instance(plat, s.cfg.name)
    load = inst.last_load.trace
    t_first = cold.t_arrival + cold.ttft_s
    c.check(f"{label}: cold generation's first token inside the load",
            cold.cold and load.t0 <= t_first <= load.t_end,
            f"TTFT {cold.ttft_s:.3f}s, first token "
            f"{t_first - load.t0:.3f}s into a {cold.load_s:.3f}s load")
    # each new prompt length (and prefix offset) compiles its prefill
    # on the request's path, so these waves rarely overlap in the batch
    specs = [cold_spec]
    resps = [cold]
    for wave in (s.wave1, s.wave2):
        specs += [s.spec(p) for p in wave]
        resps += s.generate(router, specs[-len(wave):])
    # the join wave repeats an already-compiled prompt (same prefix hit
    # under paged KV), greedy and sampled under two seeds: all three
    # prefill quickly and must share decode steps
    inst.scheduler.reset_peaks()
    specs += [s.spec(s.p_cold)] + [s.spec(s.p_cold, seed=i) for i in (1, 2)]
    resps += s.generate(router, specs[-3:])
    st = inst.scheduler.stats()
    n_tok = sum(r.n_generated for r in resps)
    tpot = [dt for r in resps for dt in r.tpot_s]
    print(f"{label}: {n_tok} tokens in {len(resps)} requests, "
          f"median inter-token {np.median(tpot) * 1e3:.1f}ms, "
          f"scheduler {st}", flush=True)
    c.check(f"{label}: warm requests joined one batch",
            all(not r.cold for r in resps[1:]) and st["max_occupancy"] >= 2,
            f"join-wave max occupancy {st['max_occupancy']}")
    if "kv_prefix_hits" in st:
        c.check(f"{label}: shared prefix served from cached pages",
                st["kv_prefix_hits"] >= 2, f"hits {st['kv_prefix_hits']}")
    toks = [[int(t) for t in r.tokens] for r in resps]
    c.check(f"{label}: same greedy request bit-identical at another "
            f"batch occupancy", toks[-3] == toks[4],
            "" if toks[-3] == toks[4] else _first_diff(toks[-3], toks[4]))
    n_same = 0
    for i, (sp, got) in enumerate(zip(specs, toks)):
        want = s.reference(inst.params, sp)
        n_same += got == want
        lg = s.forced_logits(inst.params, sp.prompt, got)
        gaps = score_gaps(sp, got, lg)
        tol = token_gap_tol(s.cfg.n_layers, lg)
        worst = int(np.argmax(gaps / tol))
        c.check(f"{label}: request {i} ({len(sp.prompt)}-token prompt, "
                f"{'cold' if resps[i].cold else 'warm'}, "
                f"{'sampled' if sp.temperature else 'greedy'}) "
                f"agrees with reference_generate",
                bool((gaps <= tol).all()),
                ("identical" if got == want else
                 f"differs from token {_first_index(got, want)}")
                + f"; reference picks another token at "
                f"{int((gaps > 0).sum())}/{len(gaps)} forced steps, "
                f"largest gap {gaps.max():.3e}, closest to its limit at "
                f"step {worst}: {gaps[worst]:.3e} (tol {tol[worst]:.3e})")
    print(f"{label}: {n_same}/{len(specs)} token streams identical to "
          f"reference_generate", flush=True)
    s.identical[label] = (n_same, len(specs))
    return toks[0]


def token_gap_tol(n_layers: int, logits) -> np.ndarray:
    """Per-step limit on a served token's score gap (logit units)
    below the reference's best.  Two bf16 evaluations that round in a
    different order disagree by an error field whose relative RMS is
    at most :func:`logit_tol`; a token whose gap is within twice that
    error's RMS at that step is a near-tie the reference could also
    have picked.  A wrong formula or a stale cache row puts the served
    token far down the reference's ranking."""
    rms = np.sqrt(np.mean(np.square(logits, dtype=np.float64), axis=-1))
    return 2.0 * logit_tol(n_layers) * rms


def check_batch_shape(c: Checks, s: Smoke, params, tokens):
    """Teacher-forced decode of one request at batch 1 (the serial
    reference), at batch 4 with the other rows empty, and at batch 4
    with every row busy: which of these are bit-identical on this
    backend."""
    one = s.forced_logits(params, s.p_cold, tokens)
    empty = s.forced_logits(params, s.p_cold, tokens, rows=4)
    busy = s.forced_logits(params, s.p_cold, tokens, rows=4, fill_rows=True)
    for name, a, b in (("batch 4 vs batch 1", empty, one),
                       ("batch 4 busy vs batch 4 empty", busy, empty)):
        print(f"decode logits, {name}: "
              f"{'bit-identical' if np.array_equal(a, b) else 'differ'}, "
              f"max |diff| {np.abs(a - b).max():.3e}, relative RMS "
              f"{rel_err(a, b):.3e} over {len(tokens)} steps", flush=True)
    c.check("decode step bit-identical across batch occupancy",
            np.array_equal(busy, empty))
    tol = logit_tol(s.cfg.n_layers)
    c.check("decode logits at batch 4 vs batch 1", rel_err(empty, one) <= tol,
            f"relative RMS {rel_err(empty, one):.3e} (tol {tol:.3f})")


def run_single(c: Checks, s: Smoke):
    plat = s.platform()
    with plat.router(workers=4) as router:
        check_oneshot_cold(c, s, plat, router)
        plat.sweep(1e9)                # keep-alive lapsed: cold again
        toks = check_generation(c, s, plat, router, "slotted")
        check_batch_shape(c, s, _instance(plat, s.cfg.name).params, toks)
    del plat
    gc.collect()                       # free the first instance's params
    plat = s.platform(kv_page_tokens=s.page_tokens)
    with plat.router(workers=4) as router:
        check_generation(c, s, plat, router, f"paged pt={s.page_tokens}")


def check_kernels(c: Checks, expect: str, before,
                  kernels=MAIN_PATH_KERNELS):
    """Every kernel of ``kernels`` was dispatched (at trace time) in
    mode ``expect`` only, since the ``before`` dispatch snapshot."""
    desc = ops.registry.describe()
    counts = {k: n - before.get(k, 0)
              for k, n in ops.registry.dispatch_snapshot().items()}
    print("registry.describe():", json.dumps(desc), flush=True)
    print("trace-time dispatch counts:",
          json.dumps({f"{k}/{m}": n for (k, m), n in
                      sorted(counts.items())}), flush=True)
    for k in kernels:
        modes = {m for (kk, m), n in counts.items() if kk == k and n}
        c.check(f"kernel {k} dispatched as {expect} only",
                modes == {expect} and desc[k]["mode"] == expect,
                f"dispatched modes {sorted(modes)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def device_bytes(params):
    out = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def run_sharded(c: Checks, s: Smoke, n_dev: int):
    name = s.cfg.name
    one = s.platform()
    tp = s.platform(mesh_shape=(1, n_dev))
    batch = {"tokens": jnp.asarray(s.p_cold[None])}
    with one.router(workers=2) as r1, tp.router(workers=2) as r4:
        a = s.submit(r1, batch=batch).result(timeout=900)
        b = s.submit(r4, batch=batch).result(timeout=900)
        c.check("both one-shot requests cold", a.cold and b.cold,
                f"single load {a.load_s:.3f}s, mesh load {b.load_s:.3f}s")
        c.check(f"(1, {n_dev}) mesh cold logits bit-identical to one "
                f"device", np.array_equal(np.asarray(a.logits),
                                          np.asarray(b.logits)))
        by_dev = device_bytes(_instance(tp, name).params)
        total = s.store.model_nbytes(name)
        print(f"mesh params per device (bytes): {by_dev}, "
              f"f32 model {total}", flush=True)
        c.check(f"parameters spread over all {n_dev} devices",
                sorted(by_dev) == [d.id for d in jax.devices()[:n_dev]]
                and min(by_dev.values()) > 0.8 * max(by_dev.values())
                and max(by_dev.values()) < 0.75 * total,
                f"min {min(by_dev.values())}, max {max(by_dev.values())}")
        ga = s.generate(r1, [s.spec(s.wave1[0])])[0]
        gb = s.generate(r4, [s.spec(s.wave1[0])])[0]
    toks = list(ga.tokens)
    same = list(gb.tokens) == toks
    print(f"tensor-parallel warm generation "
          f"{'equals' if same else 'differs from'} single-device: "
          f"{'' if same else _first_diff(list(gb.tokens), toks)}",
          flush=True)
    la = s.forced_logits(_instance(one, name).params, s.wave1[0], toks)
    lb = s.forced_logits(_instance(tp, name).params, s.wave1[0], toks)
    err = rel_err(lb, la)
    tol = logit_tol(s.cfg.n_layers)
    c.check("tensor-parallel decode logits vs single device",
            bool(np.isfinite(lb).all()) and err <= tol,
            f"relative RMS error {err:.3e} (tol {tol:.3f}) over "
            f"{len(toks)} teacher-forced steps")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the sharded cold start on a (1, 4) "
                         "mesh against one device")
    args = ap.parse_args(argv)
    cache_dir = compile_cache.enable()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
          f"{cache_dir}; times below are smoke output, not benchmark "
          f"numbers", flush=True)
    stats = CompileStats()
    before = ops.registry.dispatch_snapshot()
    c = Checks()
    s = Smoke(get_config(MODEL, smoke=False), args.seed)
    try:
        if args.chips == 1:
            c.phase("serve on one chip", run_single, s)
            check_kernels(c, "pallas", before)
        else:
            c.phase(f"sharded cold start on {args.chips} chips",
                    run_sharded, s, args.chips)
            check_kernels(c, "pallas", before, MAIN_PATH_KERNELS[:2])
    finally:
        s.cleanup()
    print(stats.line(), flush=True)
    if c.failed:
        print(f"chip_smoke: {len(c.failed)} check(s) failed: {c.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
