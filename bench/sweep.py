#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip.

    python3 bench/sweep.py --workload <name> --rates 4,8,12 --seconds 20 --seed 1

Builds and warms the cell once, then drives its mix at each rate in turn
(each with its own lead-in and window) and prints one JSON line per
rate: requests due and finished in the window, the first-token tail from
the scheduled arrival, tokens per second, and the backlog (requests due
but without a first token) a third of the way in and at the close.  The
knee is the highest rate whose backlog does not grow over the window;
the cell's mix then runs at a fixed fraction of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def backlog(records, t: float) -> int:
    return sum(1 for r in records if r.due is not None and r.due <= t
               and (r.t_first is None or r.t_first > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench.lib import harness, spec, window
    from bench.lib.compile_stats import CompileStats
    from repro import compile_cache
    if jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = spec.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    cell.traffic = dict(cell.traffic, rate_rps=max(rates))
    prog = harness.Cell(cell, args.seed, args.seconds,
                        stats=CompileStats(), log=harness.log_to_stderr)
    prog.warm_up()
    tps = spec.load_module("metrics", "tokens_per_s")
    for rate in rates:
        prog.mix = dict(cell.traffic, rate_rps=rate)
        prog.plan = prog.kind.plan(prog.mix, args.seed, args.seconds)
        run = prog.drive()
        due = [r for r in run.records if run.in_window(r.arrival)]
        ttft = [(r.t_first if r.ok else run.w1 + 60) - r.arrival
                for r in due]
        third = run.w0 + (run.w1 - run.w0) / 3
        print(json.dumps({
            "rate_rps": rate, "due": len(due),
            "finished": sum(r.ok for r in due),
            "ttft_p50_s": window.percentile(ttft, 50),
            "ttft_p95_s": window.percentile(ttft, 95),
            "tokens_per_s": tps.read(run),
            "backlog_third": backlog(run.records, third),
            "backlog_close": backlog(run.records, run.w1)}), flush=True)
    prog.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
