#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 15 [--control]

For each seed, in one process (so set-up compiles once): the cell's own
set-up, a window at the cell's own load, then the check that a run makes
-- the widest gap of a served token below the reference's best -- and
with ``--control`` the same number for the control, the reference
computed with float8 matrix products put in the program's place.  One
JSON line per seed.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from bench.lib import harness, spec
    from bench.lib.compile_stats import CompileStats
    from repro import compile_cache
    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = spec.load_cell(args.workload)
    stats = CompileStats()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        prog = harness.Cell(cell, seed, args.seconds, stats=stats,
                            log=harness.log_to_stderr)
        prog.warm_up()
        run = prog.drive()
        prog.close()
        del prog
        chk = harness.correctness(cell, run, control=args.control)
        chk.update(seed=seed, workload=args.workload,
                   finished=sum(r.ok for r in run.records),
                   failed=sum(not r.ok for r in run.records),
                   seconds=time.monotonic() - t0)
        print(json.dumps(chk), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
