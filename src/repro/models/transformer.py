"""LM assembly: scan-stacked steady-state views + streaming unit view.

Every assigned architecture (dense / MoE / SSM / hybrid / audio-encoder /
VLM) is an :class:`LM` instance.  Layers are grouped into *pattern units*
(length-1 pattern for uniform stacks; ``(rglru, rglru, attn)`` for
Griffin) and parameters are stored stacked ``(n_units, ...)`` per pattern
slot, so the forward pass is a single ``jax.lax.scan`` regardless of
depth — this keeps HLO size ~constant for the 40-cell dry-run matrix.

The *streaming* view (``unit_names`` / ``init_unit`` / ``abstract_unit``
/ ``unit_apply`` / ``assemble``) exposes per-layer granularity for the
cold-start pipeline: the paper's L_i / W_i+A_i / E_i execution units map
to one unit here; ``stack_index`` says where each unit lands in the
steady-state representation, which the cold start fills in place, and
``assemble`` gathers it once the model is fully live.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import quant
from repro.distributed.sharding import constrain
from repro.models import griffin, layers, moe, ssm
from repro.models.api import ArchConfig, Family

PyTree = Any
AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# per-kind block param/apply/cache/decode dispatch
# ---------------------------------------------------------------------------

def _kind_window(cfg, kind: str) -> int:
    if kind == "local_attn":
        return cfg.local_attn_window
    return cfg.sliding_window


def block_params(cfg, kind: str, key: jax.Array) -> PyTree:
    ks = jax.random.split(key, 4)
    if kind in ("attn", "local_attn"):
        return {"norm1": layers.norm_params(cfg, ks[0]),
                "attn": layers.attn_params(cfg, ks[1]),
                "norm2": layers.norm_params(cfg, ks[2]),
                "mlp": layers.mlp_params(cfg, ks[3])}
    if kind == "moe":
        return {"norm1": layers.norm_params(cfg, ks[0]),
                "attn": layers.attn_params(cfg, ks[1]),
                "norm2": layers.norm_params(cfg, ks[2]),
                "moe": moe.moe_params(cfg, ks[3])}
    if kind == "ssd":
        return {"norm1": layers.norm_params(cfg, ks[0]),
                "ssd": ssm.ssd_params(cfg, ks[1])}
    if kind == "rglru":
        return {"norm1": layers.norm_params(cfg, ks[0]),
                "rglru": griffin.rglru_params(cfg, ks[1]),
                "norm2": layers.norm_params(cfg, ks[2]),
                "mlp": layers.mlp_params(cfg, ks[3])}
    raise ValueError(kind)


def block_apply(cfg, kind: str, p: PyTree, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "local_attn"):
        h = layers.apply_norm(cfg, p["norm1"], x)
        x = x + layers.attention_block(cfg, p["attn"], h, positions,
                                       window=_kind_window(cfg, kind))
        h = layers.apply_norm(cfg, p["norm2"], x)
        x = x + layers.mlp_block(cfg, p["mlp"], h)
    elif kind == "moe":
        h = layers.apply_norm(cfg, p["norm1"], x)
        x = x + layers.attention_block(cfg, p["attn"], h, positions,
                                       window=cfg.sliding_window)
        h = layers.apply_norm(cfg, p["norm2"], x)
        y, aux = moe.moe_block(cfg, p["moe"], h)
        x = x + y
    elif kind == "ssd":
        h = layers.apply_norm(cfg, p["norm1"], x)
        x = x + ssm.ssd_block(cfg, p["ssd"], h)
    elif kind == "rglru":
        h = layers.apply_norm(cfg, p["norm1"], x)
        x = x + griffin.rglru_block(cfg, p["rglru"], h)
        h = layers.apply_norm(cfg, p["norm2"], x)
        x = x + layers.mlp_block(cfg, p["mlp"], h)
    else:
        raise ValueError(kind)
    return x, aux


def kind_cache(cfg, kind: str, batch: int, cache_len: int) -> PyTree:
    """Zeroed decode cache for one layer of this kind."""
    if kind in ("attn", "local_attn", "moe"):
        w = _kind_window(cfg, kind)
        n = min(cache_len, w) if w > 0 else cache_len
        # kv-head-major: dh is the minor dim for both attention dots
        shape = (batch, cfg.n_kv_heads, n, cfg.dh)
        return {"k": jnp.zeros(shape, cfg.compute_dtype),
                "v": jnp.zeros(shape, cfg.compute_dtype)}
    if kind == "ssd":
        conv, state = ssm.init_states(cfg, batch)
        return {"conv": conv, "ssm": state}
    if kind == "rglru":
        conv, h = griffin.init_states(cfg, batch)
        return {"conv": conv, "h": h}
    raise ValueError(kind)


def block_decode(cfg, kind: str, p: PyTree, x: jax.Array, pos: jax.Array,
                 cache: PyTree) -> Tuple[jax.Array, PyTree]:
    """Single-token decode.  x: (B, 1, d); pos: (B,)."""
    if kind in ("attn", "local_attn", "moe"):
        w = _kind_window(cfg, kind)
        h = layers.apply_norm(cfg, p["norm1"], x)
        y, kc, vc = layers.attention_decode(cfg, p["attn"], h, pos,
                                            cache["k"], cache["v"], window=w)
        x = x + y
        cache = {"k": kc, "v": vc}
        h = layers.apply_norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, _ = moe.moe_block(cfg, p["moe"], h)
            x = x + y
        else:
            x = x + layers.mlp_block(cfg, p["mlp"], h)
    elif kind == "ssd":
        h = layers.apply_norm(cfg, p["norm1"], x)
        y, conv, state = ssm.ssd_decode(cfg, p["ssd"], h, cache["conv"],
                                        cache["ssm"])
        x = x + y
        cache = {"conv": conv, "ssm": state}
    elif kind == "rglru":
        h = layers.apply_norm(cfg, p["norm1"], x)
        y, conv, hs = griffin.rglru_decode(cfg, p["rglru"], h, cache["conv"],
                                           cache["h"])
        x = x + y
        cache = {"conv": conv, "h": hs}
        h = layers.apply_norm(cfg, p["norm2"], x)
        x = x + layers.mlp_block(cfg, p["mlp"], h)
    else:
        raise ValueError(kind)
    return x, cache


def _kind_paged(cfg, kind: str) -> bool:
    """True when this layer kind's KV can live in a shared page pool.

    Only full-attention KV caches page: a sliding-window ring reuses
    physical slots for rolling positions (page identity would change
    under it), and SSM / RG-LRU states are O(1) per sequence — both
    stay slot-resident alongside the paged layers.
    """
    return kind in ("attn", "local_attn", "moe") and _kind_window(cfg, kind) == 0


def block_decode_paged(cfg, kind: str, p: PyTree, x: jax.Array,
                       pos: jax.Array, cache: PyTree, pages: PyTree,
                       layer: jax.Array, tables: jax.Array):
    """Single-token decode with full-attention KV served from a page pool.

    ``pages`` is the stacked ``(L, P, K, pt, 2 dh)`` pool of a paged
    kind, read and written at index ``layer``, and ``None`` for kinds
    whose state stays slot-resident (then ``cache`` is the real
    per-slot state and this defers to :func:`block_decode`).
    Returns (x, cache, pages).
    """
    if pages is None:
        x, cache = block_decode(cfg, kind, p, x, pos, cache)
        return x, cache, None
    h = layers.apply_norm(cfg, p["norm1"], x)
    y, pages = layers.attention_decode_paged(cfg, p["attn"], h, pos, pages,
                                             layer, tables)
    x = x + y
    h = layers.apply_norm(cfg, p["norm2"], x)
    if kind == "moe":
        y, _ = moe.moe_block(cfg, p["moe"], h)
        x = x + y
    else:
        x = x + layers.mlp_block(cfg, p["mlp"], h)
    return x, cache, pages


def block_chunk(cfg, kind: str, p: PyTree, x: jax.Array, cache_l: PyTree,
                off: int, cs: int) -> Tuple[jax.Array, PyTree]:
    """One full-attention block over a ``cs``-token segment starting at
    absolute position ``off``, attending to the cache prefix + itself.
    Shared by :meth:`LM.prefill_chunked` (static chunk sweep) and
    :meth:`LM.prefill_continue` (prefix-cache resume)."""
    positions = (off + jnp.arange(cs))[None, :]
    h = layers.apply_norm(cfg, p["norm1"], x)
    q, k, v = layers.qkv_project(cfg, p["attn"], h, positions)
    kc = jax.lax.dynamic_update_slice_in_dim(
        cache_l["k"], jnp.swapaxes(k, 1, 2).astype(
            cache_l["k"].dtype), off, axis=2)
    vc = jax.lax.dynamic_update_slice_in_dim(
        cache_l["v"], jnp.swapaxes(v, 1, 2).astype(
            cache_l["v"].dtype), off, axis=2)
    k_ctx = jax.lax.slice_in_dim(kc, 0, off + cs, axis=2)
    v_ctx = jax.lax.slice_in_dim(vc, 0, off + cs, axis=2)
    from repro.kernels import ops
    o = ops.flash_attention_kvmajor(q, k_ctx, v_ctx, causal=True)
    x = x + layers.attn_out(cfg, p["attn"], o)
    h = layers.apply_norm(cfg, p["norm2"], x)
    if kind == "moe":
        y, _ = moe.moe_block(cfg, p["moe"], h)
        x = x + y
    else:
        x = x + layers.mlp_block(cfg, p["mlp"], h)
    return x, {"k": kc, "v": vc}


def block_prefill(cfg, kind: str, p: PyTree, x: jax.Array,
                  positions: jax.Array, cache: PyTree
                  ) -> Tuple[jax.Array, PyTree]:
    """Full-sequence forward that also fills this layer's decode cache."""
    if kind in ("attn", "local_attn", "moe"):
        w = _kind_window(cfg, kind)
        h = layers.apply_norm(cfg, p["norm1"], x)
        y, k, v = layers.attention_block(cfg, p["attn"], h, positions,
                                         window=w, return_kv=True)
        x = x + y
        S = k.shape[1]
        W_c = cache["k"].shape[2]
        n = min(S, W_c)
        slots = (S - n + jnp.arange(n)) % W_c
        k_t = jnp.swapaxes(k[:, S - n:], 1, 2)       # one-time (B,K,n,dh)
        v_t = jnp.swapaxes(v[:, S - n:], 1, 2)
        cache = {"k": cache["k"].at[:, :, slots].set(
                     k_t.astype(cache["k"].dtype)),
                 "v": cache["v"].at[:, :, slots].set(
                     v_t.astype(cache["v"].dtype))}
        h = layers.apply_norm(cfg, p["norm2"], x)
        if kind == "moe":
            y, _ = moe.moe_block(cfg, p["moe"], h)
            x = x + y
        else:
            x = x + layers.mlp_block(cfg, p["mlp"], h)
    elif kind == "ssd":
        h = layers.apply_norm(cfg, p["norm1"], x)
        y, (conv, state) = ssm.ssd_block(cfg, p["ssd"], h,
                                         return_state=True)
        x = x + y
        cache = {"conv": conv.astype(cache["conv"].dtype), "ssm": state}
    elif kind == "rglru":
        h = layers.apply_norm(cfg, p["norm1"], x)
        y, (conv, hs) = griffin.rglru_block(cfg, p["rglru"], h,
                                            return_state=True)
        x = x + y
        cache = {"conv": conv.astype(cache["conv"].dtype), "h": hs}
        h = layers.apply_norm(cfg, p["norm2"], x)
        x = x + layers.mlp_block(cfg, p["mlp"], h)
    else:
        raise ValueError(kind)
    return x, cache


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class LM:
    """One architecture = config + pure functions over a param pytree."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.pattern, self.n_units, self.tail_kinds = self._groups(cfg)
        self._abstract_units: Dict[str, PyTree] = {}

    @staticmethod
    def _groups(cfg) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
        kinds = cfg.layer_kinds()
        if cfg.family == Family.HYBRID:
            pat = tuple(cfg.block_pattern or ("rglru", "rglru", "attn"))
        else:
            pat = (kinds[0],)
        u = len(pat)
        n_units = len(kinds) // u
        tail = tuple(kinds[n_units * u:])
        return pat, n_units, tail

    # -- layer index helpers ------------------------------------------------
    def layer_kind(self, j: int) -> str:
        u = len(self.pattern)
        if j < self.n_units * u:
            return self.pattern[j % u]
        return self.tail_kinds[j - self.n_units * u]

    # ------------------------------------------------------------------ init
    def _embed_params(self, key: jax.Array) -> PyTree:
        cfg = self.cfg
        if cfg.family == Family.AUDIO:
            return {"proj": layers.dense_init(
                key, (cfg.frontend_dim, cfg.d_model), cfg.param_dtype,
                fan_in=cfg.frontend_dim)}
        if cfg.family == Family.VLM:
            k1, k2 = jax.random.split(key)
            return {"tok": layers.embed_init(
                        k1, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
                    "mm_proj": layers.dense_init(
                        k2, (cfg.frontend_dim, cfg.d_model), cfg.param_dtype,
                        fan_in=cfg.frontend_dim)}
        return layers.embed_params(cfg, key)

    def _final_params(self, key: jax.Array) -> PyTree:
        cfg = self.cfg
        p = {"norm": layers.norm_params(cfg, key)}
        if cfg.is_encoder:
            p["head"] = {"w": layers.dense_init(
                key, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)}
        elif not cfg.tie_embeddings:
            p["head"] = {"w": layers.dense_init(
                key, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)}
        return p

    def init(self, key: jax.Array) -> PyTree:
        cfg = self.cfg
        u = len(self.pattern)
        keys = jax.random.split(key, cfg.n_layers + 2)
        blocks: Dict[str, PyTree] = {}
        for slot, kind in enumerate(self.pattern):
            per = [block_params(cfg, kind, keys[i * u + slot])
                   for i in range(self.n_units)]
            blocks[f"s{slot}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *per)
        for t, kind in enumerate(self.tail_kinds):
            blocks[f"t{t}"] = block_params(cfg, kind,
                                           keys[self.n_units * u + t])
        return {"embed": self._embed_params(keys[-2]),
                "blocks": blocks,
                "final": self._final_params(keys[-1])}

    def abstract(self) -> PyTree:
        return jax.eval_shape(
            lambda: self.init(jax.random.key(0)))

    # --------------------------------------------------------------- embed
    def embed(self, params: PyTree, batch: Dict[str, jax.Array]
              ) -> jax.Array:
        cfg = self.cfg
        p = params["embed"]
        cd = cfg.compute_dtype
        if cfg.family == Family.AUDIO:
            x = quant.einsum("bsf,fd->bsd", batch["frames"].astype(cd),
                             p["proj"], cd)
        elif cfg.family == Family.VLM:
            img = quant.einsum("bnf,fd->bnd", batch["img"].astype(cd),
                               p["mm_proj"], cd)
            tok = quant.gather_rows(p["tok"], batch["tokens"], cd)
            x = jnp.concatenate([img, tok], axis=1)
        else:
            x = quant.gather_rows(p["tok"], batch["tokens"], cd)
        return constrain(x, "batch", "seq", "embed")

    def _head(self, params: PyTree, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = layers.apply_norm(cfg, params["final"]["norm"], x)
        cd = cfg.compute_dtype
        if cfg.tie_embeddings and not cfg.is_encoder:
            # tied head contracts the table's *scaled* axis — a per-vocab
            # scale cannot ride a (d, v) matmul, so dequant (QuantLeaf
            # .astype is the transparent fallback) and transpose.
            w = params["embed"]["tok"].astype(cd).T
            logits = jnp.einsum("bsd,dv->bsv", x, w)
        else:
            logits = quant.einsum("bsd,dv->bsv", x,
                                  params["final"]["head"]["w"], cd)
        logits = constrain(logits, "batch", "seq", "vocab")
        if cfg.logit_softcap > 0:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return logits

    # -------------------------------------------------------------- forward
    def forward(self, params: PyTree, batch: Dict[str, jax.Array],
                *, remat: bool = False, unroll: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
        """Full forward.  Returns (logits (B, S, V), aux_loss).

        unroll=True replaces the layer scan with a Python loop — used by
        the roofline dry-run (XLA's cost analysis visits a while body
        once, so scanned costs would undercount by the trip count).
        """
        cfg = self.cfg
        x = self.embed(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)[None, :]
        pat = self.pattern

        def body(carry, slices):
            x, aux = carry
            for slot, kind in enumerate(pat):
                x, a = block_apply(cfg, kind, slices[slot], x, positions)
                aux = aux + a
            return (x, aux), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)

        xs = tuple(params["blocks"][f"s{i}"] for i in range(len(pat)))
        carry = (x, jnp.zeros((), jnp.float32))
        if unroll:
            for i in range(self.n_units):
                carry, _ = body(carry, jax.tree.map(lambda a: a[i], xs))
        else:
            carry, _ = jax.lax.scan(body, carry, xs)
        x, aux = carry
        for t, kind in enumerate(self.tail_kinds):
            x, a = block_apply(cfg, kind, params["blocks"][f"t{t}"], x,
                               positions)
            aux = aux + a
        return self._head(params, x), aux

    def loss(self, params: PyTree, batch: Dict[str, jax.Array],
             *, remat: bool = True, unroll: bool = False
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.forward(params, batch, remat=remat, unroll=unroll)
        labels = batch["labels"]
        V = logits.shape[-1]
        lg = logits.astype(jnp.float32)
        valid = labels >= 0
        lbl = jnp.where(valid, labels, 0)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lbl[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, logz - gold, 0.0)
        denom = jnp.maximum(jnp.sum(valid), 1)
        ce = jnp.sum(nll) / denom
        total = ce + AUX_LOSS_WEIGHT * aux
        return total, {"ce": ce, "aux": aux,
                       "accuracy": jnp.sum(
                           (jnp.argmax(lg, -1) == lbl) & valid) / denom}

    # ------------------------------------------------------- decode + cache
    def init_cache(self, batch: int, cache_len: int) -> PyTree:
        cfg = self.cfg
        caches: Dict[str, PyTree] = {}
        for slot, kind in enumerate(self.pattern):
            per = [kind_cache(cfg, kind, batch, cache_len)
                   for _ in range(self.n_units)]
            caches[f"s{slot}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        for t, kind in enumerate(self.tail_kinds):
            caches[f"t{t}"] = kind_cache(cfg, kind, batch, cache_len)
        return caches

    def abstract_cache(self, batch: int, cache_len: int) -> PyTree:
        return jax.eval_shape(lambda: self.init_cache(batch, cache_len))

    def prefill(self, params: PyTree, batch: Dict[str, jax.Array],
                cache: PyTree, *, unroll: bool = False
                ) -> Tuple[jax.Array, PyTree]:
        """Run the full prompt, fill the cache.  Returns (logits, cache):
        the logits of the last position alone, (B, 1, V), the only ones
        a request samples from; a head over every position would cost
        S x V more work and output."""
        cfg = self.cfg
        x = self.embed(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)[None, :]
        pat = self.pattern

        def body(x, inp):
            slices, csl = inp
            new_c = []
            for slot, kind in enumerate(pat):
                x, c2 = block_prefill(cfg, kind, slices[slot], x, positions,
                                      csl[slot])
                new_c.append(c2)
            return x, tuple(new_c)

        xs = tuple(params["blocks"][f"s{i}"] for i in range(len(pat)))
        cs = tuple(cache[f"s{i}"] for i in range(len(pat)))
        x, new_caches = self._scan_units(body, x, (xs, cs), unroll)
        out_cache = {f"s{i}": new_caches[i] for i in range(len(pat))}
        for t, kind in enumerate(self.tail_kinds):
            x, c2 = block_prefill(cfg, kind, params["blocks"][f"t{t}"], x,
                                  positions, cache[f"t{t}"])
            out_cache[f"t{t}"] = c2
        return self._head(params, x[:, -1:]), out_cache

    def _scan_units(self, body, carry, xs, unroll: bool):
        """scan over the stacked pattern units, or a Python loop when
        unrolled (roofline lowering); ys are re-stacked to match."""
        if not unroll:
            return jax.lax.scan(body, carry, xs)
        ys = []
        for i in range(self.n_units):
            carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
        return carry, stacked

    def prefill_chunked(self, params: PyTree, batch: Dict[str, jax.Array],
                        cache: PyTree, *, chunk: int = 2048,
                        unroll: bool = False) -> Tuple[jax.Array, PyTree]:
        """Chunked prefill for full-attention decoder LMs (§Perf): the
        prompt is processed in ``chunk``-token segments, each attending
        to the cache prefix + itself.  Peak attention memory falls from
        O(S^2) to O(chunk * S) and MoE dispatch capacity scales with the
        chunk — the difference between a 480B MoE prefill fitting HBM
        or not.  Segment offsets are static (Python loop), so every
        cache read is a static slice.
        """
        cfg = self.cfg
        assert cfg.sliding_window == 0 and not cfg.is_encoder and \
            cfg.family not in (Family.SSM, Family.HYBRID), \
            "chunked prefill: full-attention decoder LMs only"
        tokens = batch["tokens"]
        if cfg.family == Family.VLM:
            x_all = self.embed(params, batch)
        else:
            x_all = self.embed(params, {"tokens": tokens})
        S = x_all.shape[1]
        chunk = min(chunk, S)
        assert S % chunk == 0, (S, chunk)
        pat = self.pattern

        xs = tuple(params["blocks"][f"s{i}"] for i in range(len(pat)))
        logits = None
        for ci in range(S // chunk):
            off = ci * chunk
            x = jax.lax.slice_in_dim(x_all, off, off + chunk, axis=1)

            def body(x, inp, _off=off):
                slices, csl = inp
                new_c = []
                for slot, kind in enumerate(pat):
                    x, c2 = block_chunk(cfg, kind, slices[slot], x,
                                        csl[slot], _off, chunk)
                    new_c.append(c2)
                return x, tuple(new_c)

            cs_in = tuple(cache[f"s{i}"] for i in range(len(pat)))
            x, new_caches = self._scan_units(body, x, (xs, cs_in), unroll)
            cache = dict(cache)
            for i in range(len(pat)):
                cache[f"s{i}"] = new_caches[i]
            for t, kind in enumerate(self.tail_kinds):
                x, c2 = block_chunk(cfg, kind, params["blocks"][f"t{t}"], x,
                                    cache[f"t{t}"], off, chunk)
                cache[f"t{t}"] = c2
            if ci == S // chunk - 1:
                logits = self._head(params, x[:, -1:])
        return logits, cache

    def prefill_continue(self, params: PyTree, batch: Dict[str, jax.Array],
                         cache: PyTree, *, off: int, unroll: bool = False
                         ) -> Tuple[jax.Array, PyTree]:
        """Resume a full-attention prefill at absolute position ``off``:
        cache rows ``[0, off)`` already hold valid K/V (gathered from
        shared prefix pages) and ``batch["tokens"]`` is the *suffix*.
        Returns (the last position's logits, (B, 1, V), and the cache
        filled through ``off + suffix_len``).  This is the prefix-cache fast path —
        TTFT work is proportional to the unshared suffix only.
        """
        cfg = self.cfg
        assert cfg.sliding_window == 0 and not cfg.is_encoder and \
            cfg.family not in (Family.SSM, Family.HYBRID), \
            "prefill_continue: full-attention decoder LMs only"
        x = self.embed(params, batch)
        cs = x.shape[1]
        pat = self.pattern

        def body(x, inp):
            slices, csl = inp
            new_c = []
            for slot, kind in enumerate(pat):
                x, c2 = block_chunk(cfg, kind, slices[slot], x, csl[slot],
                                    off, cs)
                new_c.append(c2)
            return x, tuple(new_c)

        xs = tuple(params["blocks"][f"s{i}"] for i in range(len(pat)))
        cs_in = tuple(cache[f"s{i}"] for i in range(len(pat)))
        x, new_caches = self._scan_units(body, x, (xs, cs_in), unroll)
        out_cache = {f"s{i}": new_caches[i] for i in range(len(pat))}
        for t, kind in enumerate(self.tail_kinds):
            x, c2 = block_chunk(cfg, kind, params["blocks"][f"t{t}"], x,
                                cache[f"t{t}"], off, cs)
            out_cache[f"t{t}"] = c2
        return self._head(params, x[:, -1:]), out_cache

    def decode_step(self, params: PyTree, cache: PyTree, tokens: jax.Array,
                    pos: jax.Array, *, unroll: bool = False
                    ) -> Tuple[jax.Array, PyTree]:
        """tokens: (B, 1); pos: (B,) absolute position of this token.
        Returns (logits (B, 1, V), cache)."""
        cfg = self.cfg
        if cfg.family == Family.VLM:
            batch = {"tokens": tokens,
                     "img": jnp.zeros((tokens.shape[0], 0, cfg.frontend_dim),
                                      cfg.compute_dtype)}
        else:
            batch = {"tokens": tokens}
        x = self.embed(params, batch)
        pat = self.pattern

        def body(x, inp):
            slices, csl = inp
            new_c = []
            for slot, kind in enumerate(pat):
                x, c2 = block_decode(cfg, kind, slices[slot], x, pos, csl[slot])
                new_c.append(c2)
            return x, tuple(new_c)

        xs = tuple(params["blocks"][f"s{i}"] for i in range(len(pat)))
        cs = tuple(cache[f"s{i}"] for i in range(len(pat)))
        x, new_caches = self._scan_units(body, x, (xs, cs), unroll)
        out_cache = {f"s{i}": new_caches[i] for i in range(len(pat))}
        for t, kind in enumerate(self.tail_kinds):
            x, c2 = block_decode(cfg, kind, params["blocks"][f"t{t}"], x,
                                 pos, cache[f"t{t}"])
            out_cache[f"t{t}"] = c2
        return self._head(params, x), out_cache

    # ----------------------------------------------------- paged KV decode
    def _cache_groups(self) -> List[Tuple[str, str, bool]]:
        """(key, kind, stacked) for every block cache group."""
        out = [(f"s{i}", k, True) for i, k in enumerate(self.pattern)]
        out += [(f"t{t}", k, False) for t, k in enumerate(self.tail_kinds)]
        return out

    def paged_kinds(self) -> List[str]:
        return [k for k in set(self.pattern) | set(self.tail_kinds)
                if _kind_paged(self.cfg, k)]

    @property
    def supports_prefix_cache(self) -> bool:
        """Prefix reuse needs every layer's sequence state to live in
        pages (an unshared SSM/ring state would silently diverge) and a
        token-only prompt identity (no image/audio side inputs)."""
        cfg = self.cfg
        return (not cfg.is_encoder
                and cfg.family not in (Family.AUDIO, Family.VLM, Family.VISION)
                and all(_kind_paged(cfg, k)
                        for k in set(self.pattern) | set(self.tail_kinds)))

    def kv_page_bytes(self, page_tokens: int) -> int:
        """Device bytes one page id costs across *all* paged layers
        (K and V).  0 when no layer pages (pure-SSM / ring models)."""
        cfg = self.cfg
        per = (2 * cfg.n_kv_heads * page_tokens * cfg.dh
               * jnp.dtype(cfg.compute_dtype).itemsize)
        n = sum(self.n_units if stacked else 1
                for _, kind, stacked in self._cache_groups()
                if _kind_paged(cfg, kind))
        return n * per

    def init_kv_pages(self, n_pages: int, page_tokens: int) -> PyTree:
        """Physical page pools: per paged group, one array of ``(n_units,
        n_pages, K, pt, 2 dh)`` (stacked) / ``(n_pages, K, pt, 2 dh)``
        (tail), a page's keys in lanes ``[0, dh)`` and its values in
        ``[dh, 2 dh)``: lane-dense (at dh 64 one 128-lane tile per head
        and page), so the device's default layout is the kernels'.
        Non-paged groups map to ``None``.  The caller sizes ``n_pages``
        to budget + 1 (the trailing scratch page)."""
        cfg = self.cfg
        pools: Dict[str, PyTree] = {}
        for key, kind, stacked in self._cache_groups():
            if not _kind_paged(cfg, kind):
                pools[key] = None
                continue
            shape = (n_pages, cfg.n_kv_heads, page_tokens, 2 * cfg.dh)
            if stacked:
                shape = (self.n_units,) + shape
            pools[key] = jnp.zeros(shape, cfg.compute_dtype)
        return pools

    def init_cache_paged(self, batch: int, cache_len: int) -> PyTree:
        """Slot-resident decode state with paged kinds' K/V leaves left
        as ``None`` (they live in the page pool): same tree structure as
        :meth:`init_cache`, so the per-slot join machinery applies."""
        cfg = self.cfg
        caches: Dict[str, PyTree] = {}
        for key, kind, stacked in self._cache_groups():
            if _kind_paged(cfg, kind):
                one: PyTree = {"k": None, "v": None}
            else:
                one = kind_cache(cfg, kind, batch, cache_len)
            if stacked:
                per = [one if _kind_paged(cfg, kind) else
                       kind_cache(cfg, kind, batch, cache_len)
                       for _ in range(self.n_units)]
                caches[key] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
            else:
                caches[key] = one
        return caches

    def init_request_cache(self, paged_len: int, state_len: int) -> PyTree:
        """B=1 prefill cache for paged admission: paged kinds sized to
        the request's page span (``paged_len`` rows feed
        :meth:`pack_pages`), slot-resident kinds sized to the
        scheduler's state length so the slot-join shapes match."""
        cfg = self.cfg
        caches: Dict[str, PyTree] = {}
        for key, kind, stacked in self._cache_groups():
            n = paged_len if _kind_paged(cfg, kind) else state_len
            if stacked:
                per = [kind_cache(cfg, kind, 1, n)
                       for _ in range(self.n_units)]
                caches[key] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
            else:
                caches[key] = kind_cache(cfg, kind, 1, n)
        return caches

    def strip_paged(self, cache: PyTree) -> PyTree:
        """Project a contiguous per-request cache onto the paged state
        structure: paged kinds' K/V leaves become ``None`` (their
        content transfers via :meth:`pack_pages` instead)."""
        out = dict(cache)
        for key, kind, _ in self._cache_groups():
            if _kind_paged(self.cfg, kind):
                out[key] = {"k": None, "v": None}
        return out

    def _paged_groups(self):
        """(key, stacked) of every paged group."""
        return [(key, stacked) for key, kind, stacked in self._cache_groups()
                if _kind_paged(self.cfg, kind)]

    def gather_pages(self, cache: PyTree, pools: PyTree,
                     ids: jax.Array) -> PyTree:
        """Copy physical pages ``ids`` (in logical order) into rows
        ``[0, len(ids) * pt)`` of a contiguous single-request cache —
        the device half of a prefix-cache hit."""
        m = ids.shape[0]
        out = dict(cache)
        for key, stacked in self._paged_groups():
            pool = pools[key] if stacked else pools[key][None]
            u, _, K, pt, w = pool.shape
            seg = jnp.swapaxes(pool[:, ids], 1, 2)         # (u,K,m,pt,2dh)
            seg = seg.reshape(u, 1, K, m * pt, w)
            dst = {}
            for n, half in (("k", seg[..., :w // 2]), ("v", seg[..., w // 2:])):
                c = cache[key][n]
                if not stacked:
                    half = half[0]
                dst[n] = jax.lax.dynamic_update_slice(
                    c, half.astype(c.dtype), (0,) * c.ndim)
            out[key] = dst
        return out

    def pack_pages(self, pools: PyTree, cache: PyTree, ids: jax.Array,
                   first_page: int) -> PyTree:
        """Copy contiguous cache rows ``[first_page * pt, (first_page +
        len(ids)) * pt)`` out into physical pages ``ids`` — the device
        half of admission (prompt K/V moves from the per-request prefill
        cache into the shared pool).  Whole pages, every layer's in one
        write: on a donated pool, in place."""
        from repro.kernels import ops
        m = int(ids.shape[0])
        out = dict(pools)
        for key, stacked in self._paged_groups():
            pool = pools[key] if stacked else pools[key][None]
            pt = pool.shape[-2]
            lo = first_page * pt
            k, v = ((c if stacked else c[None])[:, 0, :, lo:lo + m * pt]
                    for c in (cache[key]["k"], cache[key]["v"]))
            seg = jnp.concatenate([k, v], axis=-1)         # (u,K,m*pt,2dh)
            u, K, _, w = seg.shape
            seg = jnp.swapaxes(seg.reshape(u, K, m, pt, w), 1, 2)
            layer = jnp.repeat(jnp.arange(u, dtype=jnp.int32), m)
            pool = ops.kv_pages_write(pool, layer, jnp.tile(ids, u),
                                      jnp.zeros_like(layer),
                                      seg.reshape(u * m, K, pt, w))
            out[key] = pool if stacked else pool[0]
        return out

    def copy_page(self, pools: PyTree, src: int, dst: int) -> PyTree:
        """Device half of a copy-on-write fork: duplicate physical page
        ``src`` into ``dst`` across every paged layer."""
        out = dict(pools)
        for key, stacked in self._paged_groups():
            pool = pools[key]
            out[key] = (pool.at[:, dst].set(pool[:, src]) if stacked
                        else pool.at[dst].set(pool[src]))
        return out

    def decode_step_paged(self, params: PyTree, cache: PyTree,
                          pools: PyTree, tables: jax.Array,
                          tokens: jax.Array, pos: jax.Array,
                          *, unroll: bool = False):
        """Batched single-token decode over the shared page pool.

        cache: :meth:`init_cache_paged` state (non-paged layers only);
        pools: :meth:`init_kv_pages` arrays; tables: (B, NP) int32 page
        ids per batch row.  Returns (logits, cache, pools).
        """
        cfg = self.cfg
        if cfg.family == Family.VLM:
            batch = {"tokens": tokens,
                     "img": jnp.zeros((tokens.shape[0], 0, cfg.frontend_dim),
                                      cfg.compute_dtype)}
        else:
            batch = {"tokens": tokens}
        x = self.embed(params, batch)
        pat = self.pattern

        # the stacked pools ride the scan's carry, not its xs/ys: each
        # layer writes its rows into them in place and the kernel reads
        # its pages out of them at the layer's index, so no op slices,
        # relayouts or re-stacks a pool
        def body(carry, inp):
            x, psl = carry
            slices, csl, layer = inp
            new_c, new_p = [], []
            for slot, kind in enumerate(pat):
                x, c2, p2 = block_decode_paged(cfg, kind, slices[slot], x,
                                               pos, csl[slot], psl[slot],
                                               layer, tables)
                new_c.append(c2)
                new_p.append(p2)
            return (x, tuple(new_p)), tuple(new_c)

        xs = tuple(params["blocks"][f"s{i}"] for i in range(len(pat)))
        cs = tuple(cache[f"s{i}"] for i in range(len(pat)))
        ps = tuple(pools[f"s{i}"] for i in range(len(pat)))
        layer = jnp.arange(self.n_units, dtype=jnp.int32)
        (x, new_p), new_c = self._scan_units(body, (x, ps), (xs, cs, layer),
                                             unroll)
        out_cache = {f"s{i}": new_c[i] for i in range(len(pat))}
        out_pools = {f"s{i}": new_p[i] for i in range(len(pat))}
        for t, kind in enumerate(self.tail_kinds):
            # a tail pool is a stack of one (a bitcast, row-major)
            pool = pools[f"t{t}"]
            x, c2, p2 = block_decode_paged(
                cfg, kind, params["blocks"][f"t{t}"], x, pos,
                cache[f"t{t}"], None if pool is None else pool[None],
                jnp.int32(0), tables)
            out_cache[f"t{t}"] = c2
            out_pools[f"t{t}"] = None if p2 is None else p2[0]
        return self._head(params, x), out_cache, out_pools

    # ------------------------------------------------------- streaming view
    def unit_names(self) -> List[str]:
        return (["embed"]
                + [f"block_{j:03d}" for j in range(self.cfg.n_layers)]
                + ["final"])

    def init_unit(self, name: str, key: jax.Array) -> PyTree:
        """PISeL-faithful construction: full numerical initialization."""
        if name == "embed":
            return self._embed_params(key)
        if name == "final":
            return self._final_params(key)
        j = int(name.split("_")[1])
        return block_params(self.cfg, self.layer_kind(j), key)

    def abstract_unit(self, name: str) -> PyTree:
        """MiniLoader construction: shape/dtype structure only.

        Cached: unit structure is static per model spec, so the
        serverless platform precomputes it at deploy time (the
        eval_shape trace never sits on the cold-start critical path —
        only placeholder allocation does)."""
        if name not in self._abstract_units:
            self._abstract_units[name] = jax.eval_shape(
                lambda: self.init_unit(name, jax.random.key(0)))
        return self._abstract_units[name]

    def stack_index(self, name: str) -> Optional[Tuple[str, int]]:
        """Where unit ``name`` sits in the scan-stacked params:
        ``(slot key, index)``, or None for a unit kept whole (embed,
        final, tail blocks)."""
        if not name.startswith("block_"):
            return None
        j = int(name.split("_")[1])
        u = len(self.pattern)
        if j >= self.n_units * u:
            return None
        return f"s{j % u}", j // u

    def assemble(self, units: Dict[str, PyTree],
                 stacked: Optional[Dict[str, PyTree]] = None) -> PyTree:
        """The steady-state params from the units.  ``stacked``: slot
        key -> that slot's blocks already stacked (the cold start writes
        each unit into them in place), so only the whole units are read
        from ``units``; without it the block units are stacked here."""
        u = len(self.pattern)
        blocks: Dict[str, PyTree] = {}
        for slot in range(u):
            if stacked is not None:
                blocks[f"s{slot}"] = stacked[f"s{slot}"]
                continue
            per = [units[f"block_{i * u + slot:03d}"]
                   for i in range(self.n_units)]
            blocks[f"s{slot}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        for t in range(len(self.tail_kinds)):
            blocks[f"t{t}"] = units[f"block_{self.n_units * u + t:03d}"]
        return {"embed": units["embed"], "blocks": blocks,
                "final": units["final"]}

    def unit_apply(self, name: str, uparams: PyTree,
                   state: Dict[str, Any]) -> Dict[str, Any]:
        """Layer-wise cold-start execution (the pipeline's E_i).

        state: {"batch": inputs} before embed; {"x": activations} after.
        After the final unit, state["logits"] holds the output.
        """
        cfg = self.cfg
        if name == "embed":
            x = self.embed({"embed": uparams}, state["batch"])
            out = dict(state)
            out["x"] = x
            out["positions"] = jnp.arange(x.shape[1])[None, :]
            if cfg.tie_embeddings and not cfg.is_encoder:
                out["embed_tok"] = uparams["tok"]
            return out
        if name == "final":
            params = {"final": uparams}
            if cfg.tie_embeddings and not cfg.is_encoder:
                params["embed"] = {"tok": state["embed_tok"]}
            out = dict(state)
            out["logits"] = self._head(params, state["x"])
            return out
        j = int(name.split("_")[1])
        kind = self.layer_kind(j)
        x, _ = block_apply(cfg, kind, uparams, state["x"],
                           state["positions"])
        out = dict(state)
        out["x"] = x
        return out

    # ----------------------------------------------------------- input specs
    def input_specs(self, kind: str, seq: int, batch: int
                    ) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStruct stand-ins for every model input of a shape cell.

        kind: "train" | "prefill" | "decode".
        """
        cfg = self.cfg
        f32 = jnp.float32
        i32 = jnp.int32
        sd = jax.ShapeDtypeStruct
        if kind == "decode":
            specs = {"tokens": sd((batch, 1), i32),
                     "pos": sd((batch,), i32)}
            return specs
        if cfg.family == Family.AUDIO:
            specs = {"frames": sd((batch, seq, cfg.frontend_dim),
                                  cfg.compute_dtype)}
        elif cfg.family == Family.VLM:
            n_img = min(256, seq // 2)
            specs = {"tokens": sd((batch, seq - n_img), i32),
                     "img": sd((batch, n_img, cfg.frontend_dim),
                               cfg.compute_dtype)}
        else:
            specs = {"tokens": sd((batch, seq), i32)}
        if kind == "train":
            specs["labels"] = sd((batch, seq), i32)
        return specs


@functools.lru_cache(maxsize=None)
def _model_cache(cfg: ArchConfig) -> LM:
    return LM(cfg)


def build(cfg: ArchConfig) -> LM:
    """Build (cached) the model for a config."""
    if cfg.family == Family.VISION:
        from repro.models import vision
        return vision.build(cfg)
    return _model_cache(cfg)
