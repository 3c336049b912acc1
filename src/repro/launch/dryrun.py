import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run (and only the dry-run) builds the production meshes out of
# 512 placeholder host devices; smoke tests and benches see 1 device.
# It is a host-only tool: it never takes an accelerator from whatever
# process holds it.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, on the single-pod 16x16
mesh and the 2x16x16 multi-pod mesh:

  1. **proof compile** — jit the full (scan-stacked) step with explicit
     in/out shardings, ``.lower().compile()``; print
     ``memory_analysis()`` (fits-HBM evidence) and record the
     collective schedule;
  2. **cost compiles** (single-pod) — the same step at depth 1 and 2
     pattern-units with the layer loop *unrolled* (XLA cost analysis
     visits a while body once, so scanned costs undercount by the trip
     count); totals combine linearly:
     ``total = c1 + (n_units - 1) * (c2 - c1)``.

Outputs one JSON record per cell for ``benchmarks/roofline.py``.

Usage:
  python -m repro.launch.dryrun --all [--multi-pod] [--out dryrun.json]
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, SHAPES, ShapeCell, supported
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.api import ArchConfig, Family, get_config
from repro.training.optim import AdamW
from repro.training.train import make_train_step

PyTree = Any
HBM_PER_CHIP = 16 * 1024 ** 3          # v5e: 16 GiB


# ---------------------------------------------------------------------------
# abstract inputs + shardings per cell kind
# ---------------------------------------------------------------------------

def _cast_abstract(tree: PyTree, dtype) -> PyTree:
    def f(l):
        if jnp.issubdtype(l.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(l.shape, dtype)
        return l
    return jax.tree.map(f, tree)


def train_micro_batches(cell: ShapeCell, mesh, micro_rows: int = 2) -> int:
    """Gradient-accumulation factor: ``micro_rows`` sequences per device
    per microbatch (default 2, the realistic pod-scale configuration).
    Fewer microbatches -> fewer FSDP weight re-gathers (collective
    term) but proportionally more activation memory."""
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    m = max(1, cell.batch // (dp * micro_rows))
    while cell.batch % m:
        m -= 1
    return m


def build_cell(cfg: ArchConfig, cell: ShapeCell, mesh, *,
               unroll: bool = False,
               serve_dtype=jnp.bfloat16,
               mixed_precision: bool = False,
               micro_rows: int = 2,
               chunked_prefill: int = 0):
    """Returns (fn, abstract_args, in_shardings, out_shardings, donate).

    unroll=True is the cost-lowering mode: layer loop unrolled AND (for
    train) a single microbatch of the global batch — the caller scales
    the measured costs back up by the microbatch count.

    Perf-iteration levers (§Perf):
      mixed_precision — bf16 param copy inside the train step;
      chunked_prefill — process prompts in N-token segments
                        (full-attention decoder LMs).
    """
    model = transformer.build(cfg)
    multi_pod = "pod" in mesh.shape
    if cell.kind == "train":
        rules = shd.train_rules(multi_pod=multi_pod)
    else:
        rules = shd.serve_rules(multi_pod=multi_pod)
        # big models cannot serve with TP-16 alone: bf16 params must
        # shard the full mesh (per-layer weight gathers are the price)
        if cfg.param_count() * 2 / 16 > 8e9:
            rules.mapping["fsdp"] = ("pod", "data") if multi_pod \
                else ("data",)

    if cell.kind == "train":
        micro = train_micro_batches(cell, mesh, micro_rows)
        batch_size = cell.batch // micro if unroll else cell.batch
        specs = model.input_specs(cell.kind, cell.seq, batch_size)
        batch_sh = shd.batch_specs(specs, mesh, rules)
        params_ab = model.abstract()
        opt = AdamW(lr=1e-4)
        opt_ab = jax.eval_shape(opt.init, params_ab)
        p_sh = shd.param_specs(params_ab, mesh, rules)
        # m/v mirror the param shardings; step scalar replicated
        o_sh = type(opt_ab)(shd.replicated(mesh),
                            shd.param_specs(opt_ab.m, mesh, rules),
                            shd.param_specs(opt_ab.v, mesh, rules))
        fn = make_train_step(model, opt, remat=True,
                             micro_batches=1 if unroll else micro,
                             unroll=unroll, mixed_precision=mixed_precision)
        args = (params_ab, opt_ab, specs)
        in_sh = (p_sh, o_sh, batch_sh)
        out_sh = (p_sh, o_sh, None)
        return fn, args, in_sh, out_sh, (0, 1), rules, model

    specs = model.input_specs(cell.kind, cell.seq, cell.batch)
    batch_sh = shd.batch_specs(specs, mesh, rules)

    params_ab = _cast_abstract(model.abstract(), serve_dtype)
    p_sh = shd.param_specs(params_ab, mesh, rules)

    if cell.kind == "prefill":
        if cfg.is_encoder:
            def fn(params, batch):
                return model.forward(params, batch, unroll=unroll)[0]
            return fn, (params_ab, specs), (p_sh, batch_sh), None, (), \
                rules, model
        cache_ab = model.abstract_cache(cell.batch, cell.seq)
        c_sh = shd.cache_specs(cache_ab, mesh, rules)

        chunkable = (chunked_prefill > 0 and cfg.sliding_window == 0
                     and cfg.family not in (Family.SSM, Family.HYBRID))
        if chunkable:
            def fn(params, batch, cache):
                return model.prefill_chunked(params, batch, cache,
                                             chunk=chunked_prefill,
                                             unroll=unroll)
        else:
            def fn(params, batch, cache):
                return model.prefill(params, batch, cache, unroll=unroll)
        return fn, (params_ab, specs, cache_ab), (p_sh, batch_sh, c_sh), \
            None, (2,), rules, model

    # decode
    cache_ab = model.abstract_cache(cell.batch, cell.seq)
    c_sh = shd.cache_specs(cache_ab, mesh, rules)

    def fn(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, unroll=unroll)
    args = (params_ab, cache_ab, specs["tokens"], specs["pos"])
    in_sh = (p_sh, c_sh, batch_sh["tokens"], batch_sh["pos"])
    return fn, args, in_sh, None, (1,), rules, model


def _reduced_cfg(cfg: ArchConfig, n_units: int) -> ArchConfig:
    if cfg.family == Family.HYBRID:
        u = len(cfg.block_pattern or ("rglru", "rglru", "attn"))
    else:
        u = 1
    tail = cfg.n_layers % u
    return dataclasses.replace(cfg, n_layers=n_units * u + tail)


def _n_units(cfg: ArchConfig) -> int:
    if cfg.family == Family.HYBRID:
        u = len(cfg.block_pattern or ("rglru", "rglru", "attn"))
    else:
        u = 1
    return cfg.n_layers // u


# ---------------------------------------------------------------------------
# per-cell dry run
# ---------------------------------------------------------------------------

def _compile(cfg, cell, mesh, *, unroll: bool, **opt_flags):
    fn, args, in_sh, out_sh, donate, rules, model = build_cell(
        cfg, cell, mesh, unroll=unroll, **opt_flags)
    with shd.use_rules(mesh, rules):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def _memory_record(compiled) -> Dict[str, Any]:
    m = compiled.memory_analysis()
    rec = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        rec[k] = int(getattr(m, k, 0))
    live = rec["argument_size_in_bytes"] + rec["temp_size_in_bytes"] \
        + rec["output_size_in_bytes"] - rec["alias_size_in_bytes"]
    rec["live_bytes_per_device"] = live
    rec["fits_hbm_16g"] = bool(live <= HBM_PER_CHIP)
    return rec


def _cost_record(compiled) -> Dict[str, Any]:
    ca = compiled.cost_analysis()
    coll = hlo_analysis.collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": coll}


def dryrun_cell(arch: str, shape: str, *, multi_pod: bool = False,
                costs: bool = True, smoke: bool = False,
                opts: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    opts = opts or {}
    cfg = get_config(arch, smoke=smoke)
    cell = SHAPES[shape]
    if smoke:
        cell = dataclasses.replace(cell, seq=min(cell.seq, 128),
                                   batch=min(cell.batch, 32))
    rec: Dict[str, Any] = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi_pod else "16x16"}
    if opts:
        rec["opts"] = dict(opts)
    ok, reason = supported(cfg, cell)
    if not ok:
        rec["status"] = "skip"
        rec["skip_reason"] = reason
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        rec["devices"] = int(mesh.size)
        t0 = time.monotonic()
        _, compiled = _compile(cfg, cell, mesh, unroll=False, **opts)
        rec["compile_s"] = round(time.monotonic() - t0, 2)
        rec["memory"] = _memory_record(compiled)
        # collective schedule of the production (scanned) program — counts
        # are per-trip; roofline uses the unrolled cost compiles below.
        rec["scan_collectives"] = hlo_analysis.collective_bytes(
            compiled.as_text())["_counts"]
        del compiled

        if costs:
            t0 = time.monotonic()
            c1 = _cost_record(_compile(_reduced_cfg(cfg, 1), cell, mesh,
                                       unroll=True, **opts)[1])
            c2 = _cost_record(_compile(_reduced_cfg(cfg, 2), cell, mesh,
                                       unroll=True, **opts)[1])
            rec["cost_compile_s"] = round(time.monotonic() - t0, 2)
            n = _n_units(cfg)
            cost = hlo_analysis.combine_linear(c1, c2, n)
            if cell.kind == "train":
                # cost compiles ran ONE microbatch; scale to the full step
                micro = train_micro_batches(
                    cell, mesh, opts.get("micro_rows", 2))
                cost = hlo_analysis.scale_cost(cost, micro)
                rec["micro_batches"] = micro
            rec["cost_per_device"] = cost
            rec["n_units"] = n
        rec["status"] = "ok"
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def iter_cells():
    for arch in ASSIGNED:
        for shape in SHAPES:
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-costs", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (machinery self-test)")
    ap.add_argument("--mixed-precision", action="store_true",
                    help="perf lever: bf16 param copy in the train step")
    ap.add_argument("--micro-rows", type=int, default=2,
                    help="perf lever: sequences/device/microbatch")
    ap.add_argument("--chunked-prefill", type=int, default=0,
                    help="perf lever: prefill segment length (0 = off)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    opts = {}
    if args.mixed_precision:
        opts["mixed_precision"] = True
    if args.micro_rows != 2:
        opts["micro_rows"] = args.micro_rows
    if args.chunked_prefill:
        opts["chunked_prefill"] = args.chunked_prefill
    cells = list(iter_cells()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records = []
    for arch, shape in cells:
        for mp in meshes:
            # roofline costs are a single-pod deliverable
            costs = (not args.no_costs) and not mp
            rec = dryrun_cell(arch, shape, multi_pod=mp, costs=costs,
                              smoke=args.smoke, opts=opts)
            records.append(rec)
            status = rec["status"]
            extra = ""
            if status == "ok":
                mem = rec["memory"]["live_bytes_per_device"] / 2 ** 30
                extra = f"live/dev={mem:.2f}GiB compile={rec['compile_s']}s"
                if "cost_per_device" in rec:
                    c = rec["cost_per_device"]
                    extra += (f" flops/dev={c['flops']:.3e}"
                              f" coll/dev={c['collectives']['total']:.3e}B")
            elif status == "skip":
                extra = rec["skip_reason"]
            else:
                extra = rec["error"]
            print(f"[{rec['mesh']:7s}] {arch:18s} {shape:12s} {status:5s} "
                  f"{extra}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    n_fail = sum(r["status"] == "fail" for r in records)
    if n_fail:
        raise SystemExit(f"{n_fail} cells FAILED")


if __name__ == "__main__":
    main()
