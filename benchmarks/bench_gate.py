"""CI bench gate: fail the build when a BENCH artifact regresses past
tolerance against its committed baseline.

Baselines live in ``benchmarks/baselines/BENCH_<bench>.json`` — the
same schema as the artifacts (benchmarks/schema.py), seeded from a CI
run and refreshed deliberately (commit a new baseline when a change
legitimately moves a number; the diff then *shows* the movement).

Only rows registered in :data:`GATES` are compared — most bench rows
are diagnostics whose run-to-run noise would make a 15% band flap.
Each gate is (direction, tolerance):

  ``lower``   value must not rise more than tol above baseline
              (latency-shaped metrics)
  ``higher``  value must not fall more than tol below baseline
              (throughput-shaped metrics)
  ``floor``   value must stay >= tol, baseline-independent (invariants
              like "the autoscaler beats the no-autoscaler run")

A gated row missing from the current artifact fails (a silently
dropped metric is a regression in coverage); a gated row missing from
the *baseline* is reported and skipped, so adding a gate and seeding
its baseline can land in one commit.  Artifacts with no registered
gates are schema-validated only.

Usage (CI's bench-gate job):
    python benchmarks/bench_gate.py --baseline-dir benchmarks/baselines \
        BENCH_trace.json BENCH_generate.json BENCH_slo.json
Exit: 0 ok, 1 regression/malformed, 2 usage.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import schema

DEFAULT_TOL = 0.15

# bench -> {row name: (direction, tolerance)}
GATES: Dict[str, Dict[str, Tuple[str, float]]] = {
    "trace": {
        # cold/warm-mix load latency of the paper strategy
        "trace/cicada/mean": ("lower", DEFAULT_TOL),
        "trace/cicada/cold_mean": ("lower", DEFAULT_TOL),
    },
    "generate": {
        "generate/conc1/ttft_p50_ms": ("lower", DEFAULT_TOL),
        "generate/conc8/tok_s": ("higher", DEFAULT_TOL),
        # quantized-resident serving (--compute-quant), baseline-
        # independent: fused dequant must hold decode throughput, and
        # int8 residency must buy a real memory win (quant <= 0.6x f32
        # resident bytes, expressed as f32/quant >= 1.66)
        "generate/quant/tok_s_vs_f32": ("floor", 0.9),
        "generate/quant/resident_ratio": ("floor", 1.66),
        # KV-page serving (--compute-paged), baseline-independent: a
        # prefix-cache hit must skip enough prefill to halve TTFT
        "generate/paged/prefix_ttft_speedup": ("floor", 2.0),
    },
    "slo": {
        "slo/autoscale/ttft_p50_ms": ("lower", DEFAULT_TOL),
        # the PR's headline invariant: pre-provisioning must beat the
        # bare platform's burst tail, whatever this runner's absolute
        # numbers are
        "slo/improvement/p99_ttft_ratio": ("floor", 1.0),
        "slo/autoscale/prewarms": ("floor", 1.0),
    },
    "sharded": {
        "sharded/mesh4_vs_mesh1/speedup": ("floor", 1.5),
    },
    "sharded_int8": {
        "sharded_int8/mesh4_vs_mesh1/speedup": ("floor", 1.5),
    },
    "cluster": {
        # the PR's headline invariants, baseline-independent: an N-node
        # scale-out burst over peer exchange must beat N independent
        # origin cold starts, and a second node cold-starting an
        # already-landed model must not touch the origin at all
        "cluster/peer_vs_origin/speedup": ("floor", 1.2),
        "cluster/second_node/zero_origin_reads": ("floor", 1.0),
    },
}


def _rows(obj) -> Dict[str, float]:
    return {name: float(value) for name, value, _ in obj["rows"]}


def gate_artifact(path: str, baseline_dir: str,
                  scale: float = 1.0) -> List[str]:
    """Returns failure messages (empty = pass); prints a verdict line
    per gated row.  ``scale`` multiplies relative tolerances (noisy
    shared runners can widen the band without editing the registry)."""
    obj = schema.validate_file(path)
    bench = obj["bench"]
    gates = GATES.get(bench, {})
    if not gates:
        print(f"-- {path}: bench={bench!r} has no registered gates "
              f"(schema-validated only)")
        return []
    cur = _rows(obj)
    base_path = os.path.join(baseline_dir, f"BENCH_{bench}.json")
    base: Dict[str, float] = {}
    if os.path.exists(base_path):
        base = _rows(schema.validate_file(base_path))
    else:
        print(f"-- {path}: no baseline at {base_path} "
              f"(floor gates still apply)")
    fails: List[str] = []
    for name, (direction, tol) in sorted(gates.items()):
        if name not in cur:
            fails.append(f"{path}: gated row {name!r} missing from "
                         f"artifact")
            continue
        v = cur[name]
        if direction == "floor":
            ok = v >= tol
            print(f"{'ok  ' if ok else 'FAIL'} {name}: {v:.4g} "
                  f"(floor {tol:g})")
            if not ok:
                fails.append(f"{path}: {name} = {v:.4g} below floor "
                             f"{tol:g}")
            continue
        if name not in base:
            print(f"--   {name}: {v:.4g} (no baseline row — seed it)")
            continue
        b = base[name]
        band = tol * scale
        if b == 0:
            ok = v == 0 if direction == "lower" else v >= 0
            delta = 0.0
        elif direction == "lower":
            delta = (v - b) / b
            ok = delta <= band
        else:
            delta = (b - v) / b
            ok = delta <= band
        print(f"{'ok  ' if ok else 'FAIL'} {name}: {v:.4g} vs "
              f"baseline {b:.4g} ({direction}, "
              f"regression {delta:+.1%}, band {band:.0%})")
        if not ok:
            fails.append(f"{path}: {name} regressed {delta:+.1%} "
                         f"(> {band:.0%} {direction}-band vs "
                         f"baseline {b:.4g})")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("artifacts", nargs="+", metavar="BENCH_*.json")
    ap.add_argument("--baseline-dir", default="benchmarks/baselines")
    ap.add_argument("--tolerance-scale", type=float,
                    default=float(os.environ.get(
                        "BENCH_GATE_TOLERANCE_SCALE", "1.0")),
                    help="multiply every relative tolerance band "
                         "(env: BENCH_GATE_TOLERANCE_SCALE)")
    args = ap.parse_args(argv)
    fails: List[str] = []
    for path in args.artifacts:
        try:
            fails.extend(gate_artifact(path, args.baseline_dir,
                                       args.tolerance_scale))
        except (schema.SchemaError, OSError, KeyError) as e:
            fails.append(f"{path}: {e}")
    if fails:
        print("\nbench-gate FAILED:")
        for f in fails:
            print(f"  {f}")
        return 1
    print("\nbench-gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
