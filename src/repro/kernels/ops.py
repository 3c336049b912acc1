"""Kernel dispatch registry.

Every op has three implementations:

  * **pallas**   — the TPU kernel (``<name>.py``), the deployment target;
  * **interpret**— the same kernel body executed in interpret mode (CPU
                   correctness validation; enabled in kernel tests via
                   ``REPRO_PALLAS=interpret``);
  * **ref**      — a memory-efficient pure-jnp fallback with identical
                   semantics.  This is what the CPU dry-run lowers (the
                   roofline math — FLOPs, bytes, collectives — is the
                   same), and what tests use as the "efficient oracle".
                   (``xla`` is accepted as a legacy alias.)

Dispatch goes through one :class:`KernelRegistry`:

  * **capability probing** — ``auto`` serves ``pallas`` on a TPU
    backend and ``ref`` on any other.  On a TPU, the first dispatch of
    a kernel compiles its Pallas callable once with tiny inputs; a
    compile failure raises with the compiler's message — a TPU never
    silently serves the ``ref`` fallback.  The probe runs once per
    kernel per process — never on the hot path.
  * **forcing** — ``REPRO_PALLAS`` ∈ {auto (default), pallas,
    interpret, ref} overrides ``auto``, and :func:`set_mode` (the
    ``--pallas`` launcher flag) overrides the env var.  Forcing
    ``pallas`` on a backend that cannot lower it fails loudly at call
    time — it never silently degrades.
  * **meshes** — Mosaic does not partition a kernel automatically, so
    when an operand lives on a multi-device mesh (a tensor-parallel
    instance) the kernel runs under ``shard_map`` on every device, on
    the whole, replicated operands (:func:`_on_devices`).
  * **block sizes** — tile shapes come from
    :func:`repro.configs.shapes.kernel_blocks` (one ``tpu`` profile,
    one ``interpret`` profile), not per-call literals.

Mode is resolved at *trace* time: jitted callers (the serving engine's
prefill/decode steps) bake the resolved kernel in, so set the mode
before building schedulers — :func:`fingerprint` keys caches that must
retrace on a change.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro import analysis
from repro.configs.shapes import kernel_blocks, wt_shard_tiles
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.decode_attention import (
    decode_attention_paged as _decode_paged_pallas)
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_pallas
from repro.kernels.weight_transform import weight_transform as _wt_pallas
from repro.kernels.quant_matmul import quant_matmul as _qm_pallas

NEG_INF = -1e30

MODES = ("auto", "pallas", "interpret", "ref")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: its Pallas entry point and a probe that
    compiles it with minimal inputs (run once on a TPU, cached)."""
    name: str
    pallas_fn: Callable
    probe: Callable[[], Any]


class KernelRegistry:
    """Per-process dispatch state: forced mode + kernels compiled."""

    def __init__(self):
        self._kernels: Dict[str, KernelSpec] = {}
        self._lock = analysis.make_lock("KernelRegistry._lock")
        self._compiled: set = set()                 # guarded-by: _lock
        self._forced: Optional[str] = None
        # (kernel, mode) -> trace-time dispatch count: observability
        # that a given path (e.g. the serving engine's jitted step)
        # actually routed through a kernel, and in which mode
        self.dispatch_counts: Dict[Tuple[str, str], int] = {}  # guarded-by: _lock

    def register(self, spec: KernelSpec):
        self._kernels[spec.name] = spec

    # ------------------------------------------------------------- control
    @staticmethod
    def _normalize(mode: str) -> str:
        mode = {"xla": "ref"}.get(mode, mode)     # legacy alias
        if mode not in MODES:
            raise ValueError(
                f"REPRO_PALLAS/--pallas must be one of {MODES}, "
                f"got {mode!r}")
        return mode

    def set_mode(self, mode: Optional[str]):
        """Force a dispatch mode process-wide (``--pallas`` flag).
        ``None``/'auto' restores backend-based resolution; overrides the
        ``REPRO_PALLAS`` env var."""
        self._forced = None if mode is None else self._normalize(mode)

    # ------------------------------------------------------------ resolve
    def _requested(self) -> str:
        return self._forced or self._normalize(
            os.environ.get("REPRO_PALLAS", "auto"))

    @staticmethod
    def _resolve(mode: str, backend: str) -> str:
        if mode == "auto":
            return "pallas" if backend == "tpu" else "ref"
        return mode

    def mode(self, name: str) -> str:
        """The dispatch mode a call of ``name`` takes now: ``auto`` is
        ``pallas`` on a TPU backend and ``ref`` on any other."""
        return self._resolve(self._requested(), jax.default_backend())

    def check_pallas(self, name: str) -> None:
        """Compile the kernel once on this TPU (tiny inputs, no
        execution; cached for the process lifetime).  A failure raises
        with the compiler's message — it is a bug, never a reason to
        serve another implementation."""
        with self._lock:
            if name in self._compiled:
                return
            try:
                self._kernels[name].probe()
            except Exception as e:
                raise RuntimeError(
                    f"Pallas kernel {name!r} failed to compile on "
                    f"{jax.default_backend()}: "
                    f"{type(e).__name__}: {e}") from e
            self._compiled.add(name)

    def pallas_supported(self, name: str) -> bool:
        """True on a TPU backend (after :meth:`check_pallas`), False on
        any other: only a TPU runs the kernels' Mosaic lowering."""
        if jax.default_backend() != "tpu":
            return False
        self.check_pallas(name)
        return True

    def dispatch(self, name: str) -> str:
        """:meth:`mode`, checked and counted — the op wrappers call this
        once per trace, so a TPU compiles each kernel it serves before
        baking it in, and callers can assert a path routed through a
        kernel."""
        m = self.mode(name)
        if m == "pallas" and jax.default_backend() == "tpu":
            self.check_pallas(name)
        with self._lock:
            key = (name, m)
            self.dispatch_counts[key] = self.dispatch_counts.get(key, 0) + 1
        return m

    def fingerprint(self) -> Tuple[str, str]:
        """Dispatch-cache key: (forced-or-env mode, backend) — the
        resolved per-kernel mode is a function of exactly these two."""
        return (self._requested(), jax.default_backend())

    def modes(self) -> Dict[str, str]:
        """Resolved mode per kernel."""
        return {n: self.mode(n) for n in self._kernels}

    def modes_for(self, fingerprint: Tuple[str, str]) -> Dict[str, str]:
        """Resolved mode per kernel under a saved :meth:`fingerprint` —
        exact even after a later ``set_mode``."""
        return {n: self._resolve(*fingerprint) for n in self._kernels}

    def dispatch_snapshot(self) -> Dict[Tuple[str, str], int]:
        """Consistent copy of :attr:`dispatch_counts` — the only
        sanctioned way to read it while op wrappers may be tracing on
        other threads."""
        with self._lock:
            return dict(self.dispatch_counts)

    def describe(self) -> Dict[str, Dict[str, Any]]:
        """Per-kernel dispatch report: resolved mode, and whether the
        kernel compiled on this chip (it compiles when first served).
        Compiles nothing itself."""
        with self._lock:
            compiled = set(self._compiled)
        return {n: {"mode": self.mode(n), "compiled": n in compiled}
                for n in sorted(self._kernels)}


registry = KernelRegistry()


def _on_devices(kernel: Callable, *args, **static):
    """Call a Pallas kernel on its operands' devices.  Operands on a
    multi-device mesh (their traced sharding names it) would make the
    partitioner split the kernel, which Mosaic refuses; instead each
    device runs the kernel on the whole operands, replicated, as the
    partitioner itself runs an op whose operands it does not split."""
    meshes = [jax.typeof(a).sharding.mesh for a in args if a is not None]
    mesh = next((m for m in meshes if not m.empty and m.size > 1), None)
    if mesh is None:
        return kernel(*args, **static)
    return jax.shard_map(lambda *xs: kernel(*xs, **static), mesh=mesh,
                         in_specs=PartitionSpec(), out_specs=PartitionSpec(),
                         check_vma=False)(*args)


def set_mode(mode: Optional[str]):
    """Module-level convenience for launchers: force the dispatch mode
    (auto / pallas / interpret / ref)."""
    registry.set_mode(mode)


def _blocks():
    """Active block-size profile: the interpret profile when interpret
    mode is forced, the TPU profile otherwise."""
    forced = registry._forced or os.environ.get("REPRO_PALLAS", "auto")
    return kernel_blocks(
        "interpret" if forced == "interpret" else "tpu")


def _register(name: str, pallas_fn: Callable, probe: Callable[[], Any]):
    registry.register(KernelSpec(name, pallas_fn, probe))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _xla_flash(q, k, v, *, causal: bool, window: int, bk: int = 1024):
    """Blocked online-softmax attention in pure jnp — O(S·bk) memory,
    identical math to the Pallas kernel.

    The KV-block loop is a *Python* loop (nk <= ~32 for every assigned
    cell): the lowered HLO contains no while op, so the dry-run's
    ``cost_analysis`` is exact.  Blocks that are fully masked out
    (above the causal diagonal / outside the sliding window) are
    skipped at trace time — matching the Pallas kernel's ``pl.when``
    pruning, so HLO FLOPs reflect the real kernel's work."""
    B, H, S, dh = q.shape
    K, T = k.shape[1], k.shape[2]
    rep = H // K
    bk = min(bk, T)
    if T % bk:
        pad = (-T) % bk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        Tp = T + pad
    else:
        Tp = T
    nk = Tp // bk
    q_offset = T - S

    # dots consume q/k/v in their stored dtype with f32 accumulation
    # (MXU semantics) — no materialized f32 copies of the slabs
    scale = 1.0 / float(dh) ** 0.5
    qr = q.reshape(B, K, rep, S, dh)
    qpos = q_offset + jnp.arange(S)

    m = jnp.full((B, K, rep, S), NEG_INF, jnp.float32)
    l = jnp.zeros((B, K, rep, S), jnp.float32)
    acc = jnp.zeros((B, K, rep, S, dh), jnp.float32)

    for ki in range(nk):
        k_lo = ki * bk
        # trace-time block pruning (mirrors pl.when in the kernel)
        if causal and k_lo > q_offset + S - 1:
            continue
        if causal and window > 0 and k_lo + bk - 1 <= q_offset - window:
            continue
        ks = k[:, :, k_lo:k_lo + bk]
        vs = v[:, :, k_lo:k_lo + bk]
        s = jnp.einsum("bkrsd,bktd->bkrst", qr, ks,
                       preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jnp.arange(bk)
        mask = (kpos[None, :] < T)
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
            if window > 0:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
        elif window > 0:
            mask = mask & (jnp.abs(kpos[None, :] - qpos[:, None]) < window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkrst,bktd->bkrsd", p.astype(v.dtype), vs,
            preferred_element_type=jnp.float32)
        m = m_new

    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).reshape(B, H, S, dh)
    return out.astype(q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0) -> jax.Array:
    """q: (B, S, H, dh); k, v: (B, T, K, dh) — model layout (seq-major).
    Returns (B, S, H, dh)."""
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    return flash_attention_kvmajor(q, kt, vt, causal=causal, window=window)


def flash_attention_kvmajor(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool = True, window: int = 0
                            ) -> jax.Array:
    """q: (B, S, H, dh); k, v: (B, K, T, dh) — cache layout (kv-major;
    chunked prefill attends directly against cache slices, no transpose).
    Returns (B, S, H, dh)."""
    qt = jnp.swapaxes(q, 1, 2)
    mode = registry.dispatch("flash_attention")
    kb = _blocks()
    if mode == "ref":
        o = _xla_flash(qt, k, v, causal=causal, window=window,
                       bk=kb.flash_ref_bk)
    else:
        o = _on_devices(_flash_pallas, qt, k, v, causal=causal,
                        window=window, bq=kb.flash_bq, bk=kb.flash_bk,
                        interpret=mode == "interpret")
    return jnp.swapaxes(o, 1, 2)


def _probe_flash():
    _flash_pallas.lower(
        jnp.zeros((1, 1, 128, 128), jnp.float32),
        jnp.zeros((1, 1, 128, 128), jnp.float32),
        jnp.zeros((1, 1, 128, 128), jnp.float32),
        causal=True, window=0, bq=128, bk=128).compile()


_register("flash_attention", _flash_pallas, _probe_flash)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, window: int = 0) -> jax.Array:
    """q: (B, H, dh); caches: (B, K, S_max, dh) kv-head-major;
    pos: (B,). -> (B, H, dh)."""
    mode = registry.dispatch("decode_attention")
    if mode == "ref":
        return ref.decode_attention(q, k_cache, v_cache, pos, window=window)
    return _on_devices(_decode_pallas, q, k_cache, v_cache, pos,
                       window=window, bs=_blocks().decode_bs,
                       interpret=mode == "interpret")


def _probe_decode():
    _decode_pallas.lower(
        jnp.zeros((1, 2, 128), jnp.float32),
        jnp.zeros((1, 1, 128, 128), jnp.float32),
        jnp.zeros((1, 1, 128, 128), jnp.float32),
        jnp.zeros((1,), jnp.int32), window=0, bs=128).compile()


_register("decode_attention", _decode_pallas, _probe_decode)


def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, tables: jax.Array,
                           pos: jax.Array, *, window: int = 0) -> jax.Array:
    """Block-paged decode attention: q (B, H, dh); page pools
    (P, K, pt, dh) shared across the batch; tables (B, NP) int32 page
    ids per row; pos (B,). -> (B, H, dh).

    The kernel tile divides the page size (every cache block lives
    inside one physical page).
    """
    mode = registry.dispatch("decode_attention_paged")
    if mode == "ref":
        return ref.decode_attention_paged(q, k_pages, v_pages, tables, pos,
                                          window=window)
    return _on_devices(_decode_paged_pallas, q, k_pages, v_pages, tables,
                       pos, window=window, bs=_blocks().decode_bs,
                       interpret=mode == "interpret")


def _probe_decode_paged():
    _decode_paged_pallas.lower(
        jnp.zeros((1, 2, 128), jnp.float32),
        jnp.zeros((2, 1, 128, 128), jnp.float32),
        jnp.zeros((2, 1, 128, 128), jnp.float32),
        jnp.zeros((1, 2), jnp.int32),
        jnp.zeros((1,), jnp.int32), window=0, bs=128).compile()


_register("decode_attention_paged", _decode_paged_pallas,
          _probe_decode_paged)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

def _xla_ssd(x, dt, A, B, C, *, bc: int = 128):
    """Chunked SSD in pure jnp — same decomposition as the kernel.

    The inter-chunk state pass is a *Python* loop (nc <= 128 for every
    assigned cell), so the lowered HLO has no while op and the dry-run's
    ``cost_analysis`` is exact."""
    b, nh, S, dp = x.shape
    N = B.shape[-1]
    bc = min(bc, S)
    assert S % bc == 0
    nc = S // bc

    xf = x.astype(jnp.float32).reshape(b, nh, nc, bc, dp)
    dtf = dt.astype(jnp.float32).reshape(b, nh, nc, bc)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32).reshape(b, nc, bc, N)
    Cf = C.astype(jnp.float32).reshape(b, nc, bc, N)

    da = dtf * Af[None, :, None, None]                    # (b, nh, nc, bc)
    cum = jnp.cumsum(da, axis=-1)
    li = jnp.arange(bc)[:, None]
    lj = jnp.arange(bc)[None, :]
    diff = cum[..., :, None] - cum[..., None, :]
    L = jnp.where(li >= lj, jnp.exp(diff), 0.0)           # (b,nh,nc,bc,bc)

    xd = xf * dtf[..., None]
    cb = jnp.einsum("bcin,bcjn->bcij", Cf, Bf)            # (b, nc, bc, bc)
    y_intra = jnp.einsum("bhcij,bhcjp->bhcip", cb[:, None] * L, xd)

    # inter-chunk states, sequential over chunks
    total = jnp.exp(cum[..., -1])                         # (b, nh, nc)
    rem = jnp.exp(cum[..., -1:] - cum)                    # (b, nh, nc, bc)
    upd = jnp.einsum("bhcj,bhcjp,bcjn->bhcpn", rem, xd, Bf)

    h = jnp.zeros((b, nh, dp, N), jnp.float32)
    y_inters = []
    for c in range(nc):
        c_dec = Cf[:, c][:, None] * jnp.exp(cum[:, :, c, :, None])
        y_inters.append(jnp.einsum("bhin,bhpn->bhip", c_dec, h))
        h = h * total[:, :, c, None, None] + upd[:, :, c]
    y_inter = jnp.stack(y_inters, axis=2)                 # (b, nh, nc, bc, dp)
    y = (y_intra + y_inter).reshape(b, nh, S, dp)
    return y.astype(x.dtype)


def ssd_scan(x, dt, A, B, C, *, bc: Optional[int] = None):
    """Shapes as in ref.ssd.  Returns y (b, nh, S, dp).

    S is padded up to a multiple of the chunk size with dt = 0 steps
    (decay exp(0·A) = 1, zero input -> state unaffected); the padded
    outputs are sliced off."""
    S = x.shape[2]
    bc = min(bc if bc is not None else _blocks().ssd_bc, S)
    pad = (-S) % bc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    mode = registry.dispatch("ssd_scan")
    if mode == "ref":
        y = _xla_ssd(x, dt, A, B, C, bc=bc)
    else:
        y = _on_devices(_ssd_pallas, x, dt, A, B, C, bc=bc,
                        interpret=mode == "interpret")
    return y[:, :, :S] if pad else y


def _probe_ssd():
    _ssd_pallas.lower(
        jnp.zeros((1, 1, 128, 128), jnp.float32),
        jnp.zeros((1, 1, 128), jnp.float32),
        jnp.zeros((1,), jnp.float32),
        jnp.zeros((1, 128, 128), jnp.float32),
        jnp.zeros((1, 128, 128), jnp.float32), bc=128).compile()


_register("ssd_scan", _ssd_pallas, _probe_ssd)


def ssd_step(h, x_t, dt_t, A, B_t, C_t):
    """Single-token SSD recurrence for decode.
    h (b,nh,dp,N); x_t (b,nh,dp); dt_t (b,nh); A (nh,); B_t/C_t (b,N).
    Returns (h_new, y_t (b,nh,dp))."""
    hf = h.astype(jnp.float32)
    decay = jnp.exp(dt_t.astype(jnp.float32) * A.astype(jnp.float32)[None])
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt_t.astype(jnp.float32),
                     x_t.astype(jnp.float32), B_t.astype(jnp.float32))
    h_new = hf * decay[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, C_t.astype(jnp.float32))
    return h_new.astype(h.dtype), y.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

def _xla_rglru(a, b):
    """Associative scan over the time axis — O(log S) depth, the natural
    XLA lowering of a first-order linear recurrence."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def combine(l, r):
        a_l, b_l = l
        a_r, b_r = r
        return a_l * a_r, b_l * a_r + b_r

    aa, bb = jax.lax.associative_scan(combine, (af, bf), axis=1)
    return bb.astype(a.dtype)


def rglru_scan(a, b, *, bc: Optional[int] = None):
    """a, b: (B, S, W) -> h at every step (B, S, W)."""
    mode = registry.dispatch("rglru_scan")
    if mode == "ref":
        return _xla_rglru(a, b)
    S = a.shape[1]
    bc = min(bc if bc is not None else _blocks().rglru_bc, S)
    pad = (-S) % bc
    if pad:                      # trailing pad only: earlier steps unaffected
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    y = _on_devices(_rglru_pallas, a, b, bc=bc,
                    interpret=mode == "interpret")
    return y[:, :S] if pad else y


def _probe_rglru():
    _rglru_pallas.lower(
        jnp.zeros((1, 128, 128), jnp.float32),
        jnp.zeros((1, 128, 128), jnp.float32), bc=128).compile()


_register("rglru_scan", _rglru_pallas, _probe_rglru)


def rglru_step(h, a_t, b_t):
    """h, a_t, b_t: (B, W) -> h_new."""
    return (a_t.astype(jnp.float32) * h.astype(jnp.float32)
            + b_t.astype(jnp.float32)).astype(h.dtype)


# ---------------------------------------------------------------------------
# weight transform
# ---------------------------------------------------------------------------

def weight_transform(w, scale=None, *, out_dtype=jnp.bfloat16,
                     bn: Optional[int] = None, bm: Optional[int] = None):
    """Dequant (int8 + per-col scale) or cast an (n, m) weight extent.

    Per-shard callers (the decoupler's placement lanes) pass ``bn``/
    ``bm`` from :func:`repro.configs.shapes.wt_shard_tiles` so small
    shard slices keep a multi-cell grid; defaults come from the active
    block profile."""
    kb = _blocks()
    bn = bn if bn is not None else kb.wt_bn
    bm = bm if bm is not None else kb.wt_bm
    mode = registry.dispatch("weight_transform")
    if mode == "ref":
        return ref.weight_transform(w, scale, out_dtype)
    return _on_devices(_wt_pallas, w, scale, out_dtype=out_dtype, bn=bn,
                       bm=bm, interpret=mode == "interpret")


def _probe_wt():
    # probe at the active profile's tiles — what dispatch will actually
    # lower — not hard-coded literals that can drift from KernelBlocks
    kb = _blocks()
    _wt_pallas.lower(
        jnp.zeros((kb.wt_bn, kb.wt_bm), jnp.int8),
        jnp.zeros((kb.wt_bm,), jnp.float32),
        out_dtype=jnp.bfloat16, bn=kb.wt_bn, bm=kb.wt_bm).compile()


_register("weight_transform", _wt_pallas, _probe_wt)


def wt_shard_blocks(nbytes: int) -> Tuple[int, int]:
    """(bn, bm) for a per-shard weight_transform of ``nbytes`` — thin
    re-export so decoupler-side callers need only this module."""
    return wt_shard_tiles(nbytes)


# ---------------------------------------------------------------------------
# quant matmul (w8a16: int8-resident weights, dequant fused into the dot)
# ---------------------------------------------------------------------------

def quant_matmul(x, w, scale, *, out_dtype=None,
                 bm: Optional[int] = None, bk: Optional[int] = None,
                 bn: Optional[int] = None):
    """Fused-dequant matmul over the trailing axis of ``x``.

    x: (..., k) activations; w: (k, n) int8; scale: (n,) f32
    per-column.  Leading axes of ``x`` are collapsed into the row dim
    and restored on the output (..., n).  The ``ref`` fallback is the
    dequant-then-matmul oracle — numerically identical to running
    ``weight_transform`` at load and a plain einsum at compute, so the
    quant-resident serving path degrades losslessly on backends without
    Pallas."""
    kb = _blocks()
    bm = bm if bm is not None else kb.qm_bm
    bk = bk if bk is not None else kb.qm_bk
    bn = bn if bn is not None else kb.qm_bn
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    mode = registry.dispatch("quant_matmul")
    if mode == "ref":
        out = ref.quant_matmul(x2, w, scale, out_dtype)
    else:
        out = _on_devices(_qm_pallas, x2, w, scale, out_dtype=out_dtype,
                          bm=bm, bk=bk, bn=bn, interpret=mode == "interpret")
    return out.reshape(lead + (w.shape[1],))


def _probe_qm():
    kb = _blocks()
    _qm_pallas.lower(
        jnp.zeros((kb.qm_bm, kb.qm_bk), jnp.float32),
        jnp.zeros((kb.qm_bk, kb.qm_bn), jnp.int8),
        jnp.zeros((kb.qm_bn,), jnp.float32),
        out_dtype=jnp.float32, bm=kb.qm_bm, bk=kb.qm_bk,
        bn=kb.qm_bn).compile()


_register("quant_matmul", _qm_pallas, _probe_qm)
