#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The run deploys the configuration's
weights, made on the device from ``--seed``, to a weight store in a
temporary directory; builds a ``ServerlessPlatform`` from the
configuration; warms up the shapes of this run's traffic; drives the
traffic through ``Router.submit`` for ``--seconds``; then frees the
program's state and replays a sample of what it served through the plain
reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from host records and from a profiler trace of a
span of the window.  The last line of standard output is one JSON
object; the numbers that decide ``correct`` are the last lines of
standard error and the last key of that object.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the compile cache lives at one fixed path inside the checkout; the
# program's compile_cache.enable() takes the directory from here
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

from bench.lib import spec as spec_mod  # noqa: E402

KERNELS = ("flash_attention", "decode_attention_paged")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace into this directory")
    ap.add_argument("--control", type=int, choices=[0, 1], default=0,
                    help="1: judge the control (the reference computed a "
                    "precision lower) in the program's place; the run "
                    "must come out not correct")
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def device_info(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec_mod.load_cell(args.workload)
    except (spec_mod.SpecError, OSError, KeyError, ValueError) as e:
        return fail(str(e))

    import jax
    from bench.lib import harness, peaks as peaks_mod

    if jax.default_backend() != "tpu":
        return fail(f"needs a TPU, JAX found {jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX found "
                    f"{len(devs)}")
    devs = devs[:cell.chips]
    try:
        peaks = peaks_mod.for_kind(devs[0].device_kind)
    except peaks_mod.UnknownDevice as e:
        return fail(str(e))

    from repro import compile_cache
    harness.log_to_stderr(
        f"bench: {args.workload} seed {args.seed} on {devs[0].device_kind} "
        f"x{len(devs)}, compile cache {compile_cache.enable()}")
    out = run_cell(cell, args.seed, args.seconds, args.trace, devs, peaks,
                   keep_trace=args.keep_trace, control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


def run_cell(cell, seed: int, seconds: float, trace: int, devs, peaks, *,
             kernel_mode: str = "pallas", keep_trace=None,
             log=None, control: bool = False) -> dict:
    """Everything of a run after the look for a chip: set-up, window,
    metrics and the check.  Returns the result object.  With ``control``
    the check judges the control's choices in place of the served
    tokens, by the same comparison and limit."""
    from bench.lib import harness
    from bench.lib.compile_stats import CompileStats
    from repro.kernels import ops
    log = log or harness.log_to_stderr
    stats = CompileStats()
    before = ops.registry.dispatch_snapshot()

    prog = harness.Cell(cell, seed, seconds, stats=stats, log=log)
    prog.warm_up()
    setup_s = time.monotonic() - T_START
    log(f"set-up: {setup_s:.3f}s, {stats.snapshot()}")

    tracer = None
    if trace:
        from bench.lib import trace as trace_mod
        tracer = trace_mod.Tracer(float(cell.traffic["trace_start_s"]),
                                  float(cell.traffic["trace_s"]),
                                  keep=keep_trace)
    run = prog.drive(tracer)
    run.peaks, run.setup_s = peaks, setup_s
    c = run.counters
    # compiles inside the window are reported here, never in the result
    log(f"window: {c['compile_compiles']:.0f} compiles, "
        f"{c['compile_hits']:.0f} persistent-cache loads, "
        f"{c['compile_misses']:.0f} misses, "
        f"{c['cold_starts']:.0f} cold starts, "
        f"{c['decode_steps']:.0f} decode steps, "
        f"{c['host_cpu_s']:.3f}s of host CPU, "
        f"{c['gc_collections']:.0f} garbage collections")

    counts = {k: n - before.get(k, 0)
              for k, n in ops.registry.dispatch_snapshot().items()}
    off_mode = sum(n for (k, mode), n in counts.items()
                   if k in KERNELS and mode != kernel_mode and n)
    missing = [k for k in KERNELS if not counts.get((k, kernel_mode), 0)]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    prog.close()
    del prog
    import jax
    log(f"after the window: {sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f}"
        f" GB still live on the device, peak {peak / 1e9:.3f} GB")

    metrics = {}
    device = device_info(devs, peak)
    if tracer is not None:
        from bench.lib import trace as trace_mod
        view = trace_mod.View(trace_mod.load(tracer.path()), tracer.mark)
        tracer.cleanup()
        run.trace = view
        t0, t1 = run.trace_window
        device["busy_s"] = view.busy_s(t0, t1)
        device["window_s"] = t1 - t0
    for m in (cell.per_layer if trace else cell.end_to_end):
        try:
            v = m.reader.read(run)
        except Exception as e:           # the metric is left out, and why
            log(f"metric {m.name}: {type(e).__name__}: {e}")
            continue
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    in_window = [r for r in run.records if run.in_window(r.arrival)]
    log("first token after arrival, s: " + " ".join(
        f"{r.t_first - r.arrival:.3f}" for r in in_window if r.ok))
    errors = [r.error for r in run.records if r.error]
    if errors:
        log(f"{len(errors)} requests failed; the first: {errors[0]}")
    chk = harness.correctness(cell, run, control=control)
    if control:
        log(f"control: the program's own max_gap {chk['max_gap']!r}")
    checks = {
        "max_gap": {"value": chk.get("control_max_gap" if control
                                     else "max_gap"),
                    "limit": float(cell.config["check"]["max_gap"])},
        f"kernels_not_{kernel_mode}": {"value": off_mode + len(missing),
                                       "limit": 0},
    }
    # no served token to compare is no evidence of correctness
    correct = all(v["value"] is not None and v["value"] <= v["limit"]
                  for v in checks.values())
    log(f"checked {chk['tokens']} served tokens of {chk['requests']} "
        f"requests against the reference")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    out = {"correct": correct, "attempted": len(in_window),
           "failed": sum(1 for r in in_window if not r.ok),
           "metrics": metrics, "device": device}
    if run.trace is not None:
        t0, t1 = run.trace_window
        out["breakdown"] = {"device_ops": run.trace.top_ops(t0, t1),
                            "idle_gaps": run.trace.idle_gaps(t0, t1)}
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
