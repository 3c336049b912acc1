"""Kernel micro-benchmarks: sweep the dispatch registry.

Every kernel is timed under each *available* mode — ``ref`` (the jnp
fallback serving CPU hot paths), ``interpret`` (the Pallas kernel body
executed by the interpreter: a correctness harness, timed here so its
cost trend is visible), and ``pallas`` when the backend probes as
capable (TPU).  ``weight_transform`` additionally sweeps the per-shard
extent sizes the decoupler's placement lanes feed it (full leaf down to
a 4-way shard slice), with the tile sizes
:func:`repro.configs.shapes.wt_shard_tiles` assigns each size.

``quant_matmul`` is timed at a decode shape (m=8) and a prefill shape
(m=1024) — the two regimes the fused-dequant kernel serves under
``compute_quant``.

``--autotune`` additionally sweeps the tunable block sizes of
``quant_matmul`` and ``weight_transform`` on this backend and persists
the per-kernel winner into the JSON artifact (``"autotune"`` key,
keyed by backend + sweep shape); a later run — or the serving process —
re-applies it with :func:`repro.configs.shapes.load_autotuned`.

``--json-out BENCH_kernels.json`` emits the rows plus the registry's
capability report and per-mode dispatch counts — the CI bench-smoke
artifact.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs import shapes
from repro.configs.shapes import kernel_blocks, wt_shard_tiles
from repro.kernels import ops


def timeit(f, *a, n=3):
    jax.block_until_ready(f(*a))
    t0 = time.monotonic()
    for _ in range(n):
        jax.block_until_ready(f(*a))
    return (time.monotonic() - t0) / n


def vmem_bytes_flash(bq=None, bk=None, dh=128):
    kb = kernel_blocks()
    bq = bq or kb.flash_bq
    bk = bk or kb.flash_bk
    # q + k + v + acc(f32) + m/l scratch
    return (bq * dh * 2 + 2 * bk * dh * 2 + bq * dh * 4
            + 2 * bq * 128 * 4)


def _available_modes(requested=None):
    modes = ["ref", "interpret"]
    if all(ops.registry.pallas_supported(n)
           for n in ("flash_attention", "decode_attention")):
        modes.append("pallas")
    if requested:
        missing = [m for m in requested if m not in modes]
        if missing:
            raise SystemExit(
                f"requested mode(s) {missing} unavailable on this "
                f"backend (capable of: {modes}); see "
                f"ops.registry.describe() for probe verdicts")
        modes = [m for m in modes if m in requested]
    return modes


def _sweep(rows, name, build, modes, ref_bytes=0.0):
    """Time one kernel closure under each dispatch mode.  ``build()``
    returns (fn, args): rebuilt per mode so the fresh jit traces under
    the newly-forced dispatch."""
    for mode in modes:
        ops.set_mode(mode)
        try:
            f, args = build()
            t = timeit(f, *args)
            rows.append([f"kernel/{name}/{mode}", t * 1e6,
                         ref_bytes / t / 1e9 if ref_bytes else 0.0])
        finally:
            ops.set_mode(None)


def run(args=None):
    r = np.random.default_rng(0)
    rows = []
    modes = _available_modes(getattr(args, "modes", None))

    B, H, K, S, dh = 1, 4, 2, 256, 64
    q = jnp.asarray(r.standard_normal((B, S, H, dh)), jnp.bfloat16)
    k = jnp.asarray(r.standard_normal((B, S, K, dh)), jnp.bfloat16)
    v = jnp.asarray(r.standard_normal((B, S, K, dh)), jnp.bfloat16)
    _sweep(rows, "flash_attention_256",
           lambda: (jax.jit(lambda q, k, v: ops.flash_attention(
               q, k, v, causal=True)), (q, k, v)), modes,
           ref_bytes=2 * 2 * B * H * S * S * dh)

    kc = jnp.asarray(r.standard_normal((2, K, 512, dh)), jnp.bfloat16)
    q1 = jnp.asarray(r.standard_normal((2, H, dh)), jnp.bfloat16)
    pos = jnp.full((2,), 511, jnp.int32)
    _sweep(rows, "decode_attention_512",
           lambda: (jax.jit(lambda q, a, b, p: ops.decode_attention(
               q, a, b, p)), (q1, kc, kc, pos)), modes,
           ref_bytes=kc.nbytes * 2)

    # paged twin of the 512-token decode: same logical extent gathered
    # through per-sequence page tables over a shuffled physical pool
    pt = 64
    npg = 512 // pt
    P = 2 * npg + 2                       # + 2 unreferenced pages
    perm = r.permutation(P)[:2 * npg]
    tbl = jnp.asarray(perm.reshape(2, npg).astype(np.int32))
    # one layer's pool, K and V of a page side by side in lanes
    kvp = jnp.asarray(r.standard_normal((1, P, K, pt, 2 * dh)), jnp.bfloat16)
    paged_modes = [m for m in modes if m != "pallas"
                   or ops.registry.pallas_supported("decode_attention_paged")]
    _sweep(rows, f"decode_attention_paged_512_pt{pt}",
           lambda: (jax.jit(lambda q, a, t, p:
                            ops.decode_attention_paged(q, a, t, p, 0)),
                    (q1, kvp, tbl, pos)), paged_modes,
           ref_bytes=2 * 2 * npg * pt * K * dh * kvp.dtype.itemsize)

    x = jnp.asarray(r.standard_normal((1, 4, 256, 64)), jnp.float32)
    dt = jnp.abs(jnp.asarray(r.standard_normal((1, 4, 256)),
                             jnp.float32)) * 0.1
    A = -jnp.ones((4,))
    Bm = jnp.asarray(r.standard_normal((1, 256, 64)), jnp.float32)
    _sweep(rows, "ssd_scan_256",
           lambda: (jax.jit(lambda *a: ops.ssd_scan(*a, bc=64)),
                    (x, dt, A, Bm, Bm)), modes)

    a = jnp.abs(jnp.asarray(r.standard_normal((1, 256, 128)),
                            jnp.float32)) * 0.3
    b = jnp.asarray(r.standard_normal((1, 256, 128)), jnp.float32)
    _sweep(rows, "rglru_scan_256",
           lambda: (jax.jit(ops.rglru_scan), (a, b)), modes)

    # weight transform at the shard-slice sizes the placement lanes see:
    # a 4M-element leaf whole, then its 2-way and 4-way column shards
    n_full, m_full = 2048, 2048
    w8_full = np.asarray(r.integers(-127, 128, (n_full, m_full)), np.int8)
    sc_full = np.abs(r.standard_normal(m_full).astype(np.float32)) + 1e-3
    for div in (1, 2, 4):
        m = m_full // div
        w8 = jnp.asarray(w8_full[:, :m])
        sc = jnp.asarray(sc_full[:m])
        bn, bm = wt_shard_tiles(w8.nbytes)
        _sweep(rows, f"weight_transform_shard{div}_bn{bn}",
               lambda w8=w8, sc=sc, bn=bn, bm=bm: (
                   jax.jit(lambda w, s: ops.weight_transform(
                       w, s, bn=bn, bm=bm)), (w8, sc)), modes,
               ref_bytes=w8.nbytes)

    # fused-dequant matmul at the two compute_quant regimes: decode
    # (a few resident generations' activations against one weight) and
    # prefill (prompt-length activation blocks)
    K_qm, N_qm = 1024, 1024
    w8 = jnp.asarray(r.integers(-127, 128, (K_qm, N_qm)), np.int8)
    sc = jnp.asarray(np.abs(r.standard_normal(N_qm).astype(np.float32))
                     + 1e-3)
    for m, tag in ((8, "decode"), (1024, "prefill")):
        xq = jnp.asarray(r.standard_normal((m, K_qm)), jnp.bfloat16)
        _sweep(rows, f"quant_matmul_{tag}_m{m}",
               lambda xq=xq: (jax.jit(lambda x, w, s: ops.quant_matmul(
                   x, w, s)), (xq, w8, sc)), modes,
               ref_bytes=w8.nbytes)

    autotune = None
    if getattr(args, "autotune", False):
        autotune = autotune_blocks(rows)

    # TPU-target VMEM budgets (static analysis of the configured blocks)
    rows.append(["kernel/flash_vmem_kb", vmem_bytes_flash() / 1024, 0.0])
    common.print_csv(["name", "us_per_call", "derived_gbps"], rows)

    json_out = getattr(args, "json_out", None)
    if json_out:
        obj = {"bench": "kernels",
               "header": ["name", "us_per_call", "derived_gbps"],
               "registry": ops.registry.describe(),
               "dispatch_counts": {
                   f"{k}/{m}": n for (k, m), n
                   in ops.registry.dispatch_snapshot().items()},
               "rows": rows}
        if autotune is not None:
            obj["autotune"] = autotune
        with open(json_out, "w") as f:
            json.dump(obj, f, indent=2)
        print(f"# wrote {json_out}")
    return rows


# ---------------------------------------------------------------------------
# per-backend block autotuning
# ---------------------------------------------------------------------------

# candidate grids per kernel: KernelBlocks field -> values.  Every value
# divides the sweep shapes below, so interpret-mode timing exercises the
# exact tiling (no padding) and a pallas-capable backend lowers each
# candidate unchanged.
_TUNE_GRID = {
    "quant_matmul": {"qm_bm": (128, 256), "qm_bk": (256, 512),
                     "qm_bn": (128, 256)},
    "weight_transform": {"wt_bn": (256, 512), "wt_bm": (256, 512)},
}
_TUNE_SHAPES = {"quant_matmul": (256, 1024, 1024),     # (m, k, n)
                "weight_transform": (2048, 1024)}      # (n, m)


def autotune_blocks(rows, grid=None):
    """Sweep the tunable block sizes on this backend; returns the
    ``"autotune"`` artifact section (and appends a best-time row per
    kernel).  Timed under the best *executing* mode — ``pallas`` when
    the backend probes capable, else ``interpret`` (the interpreter
    walks the real grid, so tile-count effects are visible even where
    the pallas path cannot lower)."""
    import itertools

    backend = jax.default_backend()
    grid = grid or _TUNE_GRID
    out = {}
    r = np.random.default_rng(1)
    for kern, fields in grid.items():
        mode = "pallas" if ops.registry.pallas_supported(kern) \
            else "interpret"
        if kern == "quant_matmul":
            m, k, n = _TUNE_SHAPES[kern]
            x = jnp.asarray(r.standard_normal((m, k)), jnp.bfloat16)
            w = jnp.asarray(r.integers(-127, 128, (k, n)), np.int8)
            s = jnp.asarray(np.abs(r.standard_normal(n)
                                   .astype(np.float32)) + 1e-3)

            def build(cand):
                return (jax.jit(lambda x, w, s: ops.quant_matmul(
                    x, w, s, bm=cand["qm_bm"], bk=cand["qm_bk"],
                    bn=cand["qm_bn"])), (x, w, s))
        else:
            n, m = _TUNE_SHAPES[kern]
            w = jnp.asarray(r.integers(-127, 128, (n, m)), np.int8)
            s = jnp.asarray(np.abs(r.standard_normal(m)
                                   .astype(np.float32)) + 1e-3)

            def build(cand):
                return (jax.jit(lambda w, s: ops.weight_transform(
                    w, s, bn=cand["wt_bn"], bm=cand["wt_bm"])), (w, s))

        names = list(fields)
        best = None
        ops.set_mode(mode)
        try:
            for combo in itertools.product(*(fields[f] for f in names)):
                cand = dict(zip(names, combo))
                f, fargs = build(cand)
                t = timeit(f, *fargs)
                if best is None or t < best[1]:
                    best = (cand, t)
        finally:
            ops.set_mode(None)
        out[kern] = {"backend": backend, "mode": mode,
                     "shape": list(_TUNE_SHAPES[kern]),
                     "winner": best[0], "us_per_call": best[1] * 1e6}
        rows.append([f"kernel/autotune/{kern}_best_us", best[1] * 1e6,
                     0.0])
        print(f"# autotune {kern} [{backend}/{mode}]: {best[0]} "
              f"({best[1] * 1e6:.1f}us)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json-out", default=None,
                    help="also write rows + registry capability report "
                         "as JSON (CI artifact)")
    ap.add_argument("--modes", nargs="+", default=None,
                    choices=["ref", "interpret", "pallas"],
                    help="restrict the dispatch-mode sweep")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep quant_matmul / weight_transform block "
                         "sizes on this backend and persist the winner "
                         "into the JSON artifact (reload with "
                         "repro.configs.shapes.load_autotuned)")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    main()
