"""Fig. 8 analogue: trace-driven platform replay — cold/warm mix and
per-strategy mean latency under the bursty Azure-like workload, plus

  * a concurrency sweep (serial seed-style replay vs ≥4 in-flight
    requests through the Router's worker pool),
  * a scale-out sweep for the node-local WeightCache: cold-baseline vs
    warm-cache cold-start latency, and single-flight reads under
    concurrent scale-out of one model, and
  * ``--workload generate``: the generation-first serving path —
    TTFT p50/p99, TPOT and aggregate tokens/s at concurrency {1, 4, 8}
    through one instance's continuous-batching DecodeScheduler, against
    a serial per-request prefill+decode baseline; plus a cold
    generation request whose first token must land inside the loading
    pipeline (before the final E event completes).

``--mesh`` sweeps shard-granular cold starts over simulated device
meshes of 1 / 2 / 4 (λScale-style: every device brings its own
``--bandwidth-mbps`` store channel) and reports the critical-path load
time per mesh size — the BENCH_sharded.json artifact.  ``--quant
int8`` runs the same sweep from an int8-quantized deployment: the
shard streams carry value+scale slices and the placement lanes run the
per-shard ``weight_transform`` dequant before each commit (the
BENCH_sharded_int8.json artifact).

``--workload slo`` runs the open-loop SLO bench (benchmarks/load_gen):
a mixed one-shot + generation Poisson workload with a 10x burst phase,
replayed twice from the same arrival schedule — once against a bare
platform (``slo/noscale/*`` rows) and once with the Autoscaler
pre-provisioning warm instances off the arrival-rate slope
(``slo/autoscale/*`` rows) — reporting client-perceived p99 TTFT and
per-class SLO attainment, plus the noscale/autoscale improvement ratio
(the BENCH_slo.json artifact).

``--workload cluster`` runs the multi-node bench (repro.cluster): an
all-nodes simultaneous cold-start burst at ``--nodes`` {1, 2, 4} with
peer-to-peer shard exchange over the fast intra-cluster link
(``--cluster-bw-mbps``), against the same burst with cluster-blind
nodes that each re-read the slow shared origin
(``--cluster-origin-mbps``) — plus a two-node phase proving the second
node's cold start is served entirely by its peer (zero origin reads).
The BENCH_cluster.json artifact.

``--pallas {auto,pallas,interpret,ref}`` forces the kernel dispatch
registry (default: auto — capability-probed per kernel).

Every ``--json-out`` artifact is validated against benchmarks/schema.py
before it is written (CI re-validates the files in bench-smoke).

Run directly for CI's bench-smoke job:

    PYTHONPATH=src:. python benchmarks/trace_bench.py --quick \
        --invocations 8 --json-out BENCH_trace.json
    PYTHONPATH=src:. python benchmarks/trace_bench.py --quick \
        --workload generate --models smollm-360m \
        --json-out BENCH_generate.json
    PYTHONPATH=src:. python benchmarks/trace_bench.py --quick --mesh \
        --bandwidth-mbps 200 --json-out BENCH_sharded.json
    PYTHONPATH=src:. python benchmarks/trace_bench.py --quick \
        --workload slo --models smollm-360m --json-out BENCH_slo.json
    PYTHONPATH=src:. python benchmarks/trace_bench.py --quick \
        --workload cluster --nodes 1 2 4 --json-out BENCH_cluster.json
"""
from __future__ import annotations

import json
import os
import sys
import time

# Must precede the jax import (jax locks the device count on first
# init): the --mesh sweep simulates a 4-device host mesh on CPU.
if "--mesh" in sys.argv and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np

from benchmarks import common, schema
from repro.serving.api import GenerateSpec, Request
from repro.serving.decode import reference_generate
from repro.serving.engine import ServerlessPlatform
from repro.serving.trace import Invocation, azure_like_trace, summarize


def _replay(store, models, args, trace, strat, *, concurrency=1,
            max_instances=1, keep_alive_s=45.0, cache_budget_bytes=None):
    builders = {}
    for name in models:
        cfg, model = common.get_model(name, args.quick)
        builders[name] = (lambda m=model, c=cfg:
                          (m, common.make_batch(c)))
    platform = ServerlessPlatform(store, builders, strategy=strat,
                                  keep_alive_s=keep_alive_s,
                                  max_instances=max_instances,
                                  cache_budget_bytes=cache_budget_bytes)
    rs = platform.run_trace(trace,
                            lambda n: common.make_batch(
                                common.get_model(n, args.quick)[0]),
                            concurrency=concurrency)
    return rs, platform


def scaleout_sweep(store, models, args, *, n_instances=2):
    """Cold vs warm-cache cold starts under the shared WeightCache.

    Phase rows (keep-alive expires between two invocations, so both
    are cold starts; with the cache the second one's retrieval is
    all hits):
      recold_nocache — second cold start, no cache (baseline: full re-read)
      recold_cache   — second cold start, warm cache (~zero retrieval)
    Concurrency rows (n_instances simultaneous cold starts of one
    model single-flight each unit's read):
      scaleout{N}_cold_mean + the cache's deduped-read count.
    """
    rows = []
    name = models[0]
    recold = {}
    for label, budget in (("nocache", None), ("cache", 0)):
        # 0 -> unbounded budget; None -> cache disabled
        tr = [Invocation(0.0, name, 0), Invocation(1000.0, name, 1)]
        rs, platform = _replay(store, [name], args, tr, "cicada",
                               keep_alive_s=10.0,
                               cache_budget_bytes=budget)
        assert [r.cold for r in rs] == [True, True]
        recold[label] = rs[1].latency_s
        rows.append([f"trace/cicada/recold_{label}", rs[1].latency_s * 1e6,
                     rs[0].latency_s * 1e6])
    if recold["cache"] > 0:
        rows.append(["trace/cicada/recold_speedup",
                     recold["nocache"] / recold["cache"], 0.0])
    # concurrent scale-out: n_instances cold starts at once, one store
    # read per unit node-wide
    tr = [Invocation(0.0, name, i) for i in range(n_instances)]
    rs, platform = _replay(store, [name], args, tr, "cicada",
                           concurrency=n_instances,
                           max_instances=n_instances,
                           cache_budget_bytes=0)
    lat = np.array([r.latency_s for r in rs])
    cs = platform.cache_stats()
    rows.append([f"trace/cicada/scaleout{n_instances}_cold_mean",
                 lat.mean() * 1e6, float(sum(r.cold for r in rs))])
    # every hit is a store read avoided (waits are the subset of hits
    # that blocked on a concurrent leader's in-flight read)
    rows.append([f"trace/cicada/scaleout{n_instances}_deduped_reads",
                 float(cs.hits), float(cs.misses)])
    return rows


def generate_run(args):
    """--workload generate: TTFT / TPOT / tokens-per-second rows.

    Rows (name, value, derived):
      generate/cold/ttft_ms            TTFT of a cold generation request;
                                       derived = load_s (ms) — TTFT must
                                       be smaller: first token produced
                                       inside the pipeline
      generate/cold/ttft_before_final_E 1.0 when the first-token
                                       timestamp precedes the final E
                                       event's completion in the trace
      generate/serial/tok_s            per-request serial prefill+decode
                                       baseline (reference_generate)
      generate/conc{N}/tok_s           aggregate through the Router at
                                       concurrency N, one instance
                                       (continuous batching); derived =
                                       max slot occupancy reached
      generate/conc{N}/ttft_p50_ms, ttft_p99_ms, tpot_ms
      generate/conc8/speedup_vs_serial aggregate tokens/s ratio
    """
    rows = []
    name = args.models[0]
    cfg, model = common.get_model(name, args.quick)
    if not hasattr(model, "decode_step"):
        raise SystemExit(
            f"--workload generate needs a decoder LM, got {name!r} "
            f"({cfg.family.value}); try --models smollm-360m")
    store, _ = common.deployed_store(args)
    common.ensure_deployed(store, name, args.quick)
    # enough decode steps for batching to amortize per-request
    # prefill/join overhead (short runs understate the steady state)
    n_new = args.n_new or (16 if args.quick else 32)
    prompt_len = args.prompt_len
    cache_len = max(64, prompt_len + n_new)
    rng = np.random.default_rng(0)

    def spec(i=0):
        return GenerateSpec(
            prompt=rng.integers(0, cfg.vocab_size,
                                (prompt_len,)).astype(np.int32),
            n_new=n_new, seed=i)

    def build_platform():
        return ServerlessPlatform(
            store, {name: (lambda: (model, common.make_batch(cfg)))},
            strategy="cicada", keep_alive_s=1e9, max_instances=1,
            gen_slots=8, gen_cache_len=cache_len)

    # ---- cold generation: TTFT inside the loading pipeline ----------------
    platform = build_platform()
    router = platform.router(workers=1)
    try:
        cold = router.submit(Request(req_id=0, model=name,
                                     gen=spec())).result()
    finally:
        router.shutdown()
    assert cold.cold
    inst = platform.pools[name]._instances[0]
    trace = inst.last_load.trace
    final_e_end = max(e.t_end for e in trace.events if e.stage == "E")
    # first-token absolute time = service start + ttft
    t_first_abs = cold.t_arrival + cold.ttft_s
    rows.append(["generate/cold/ttft_ms", cold.ttft_s * 1e3,
                 cold.load_s * 1e3])
    rows.append(["generate/cold/ttft_before_final_E",
                 float(t_first_abs <= final_e_end), 0.0])
    params = inst.params

    # ---- serial per-request baseline (B=1 prefill + decode loop) ----------
    n_req = args.gen_requests or (8 if args.quick else 16)
    reference_generate(model, params, spec(0).prompt, n_new=n_new,
                       cache_len=cache_len)          # jit warm
    t0 = time.monotonic()
    for i in range(n_req):
        reference_generate(model, params, spec(i).prompt, n_new=n_new,
                           cache_len=cache_len)
    serial_tok_s = n_req * n_new / (time.monotonic() - t0)
    rows.append(["generate/serial/tok_s", serial_tok_s, float(n_req)])

    # ---- continuous batching through the Router at concurrency {1,4,8} ----
    conc_tok_s = {}
    for conc in (1, 4, 8):
        router = platform.router(workers=conc)
        try:
            # warm the step/prefill compiles outside the timed window
            router.submit(Request(req_id=-1, model=name,
                                  gen=spec())).result()
            # report THIS level's peak occupancy, not the lifetime max
            inst.scheduler.reset_peaks()
            t0 = time.monotonic()
            futs = [router.submit(Request(req_id=i, model=name,
                                          gen=spec(i)))
                    for i in range(n_req)]
            rs = [f.result() for f in futs]
            wall = time.monotonic() - t0
        finally:
            router.shutdown()
        n_tok = sum(r.n_generated for r in rs)
        ttft = np.array([r.ttft_s for r in rs])
        tpot = np.concatenate([r.tpot_s for r in rs])
        occ = inst.scheduler.stats()["max_occupancy"]
        conc_tok_s[conc] = n_tok / wall
        rows.append([f"generate/conc{conc}/tok_s", n_tok / wall,
                     float(occ)])
        rows.append([f"generate/conc{conc}/ttft_p50_ms",
                     np.percentile(ttft, 50) * 1e3, 0.0])
        rows.append([f"generate/conc{conc}/ttft_p99_ms",
                     np.percentile(ttft, 99) * 1e3, 0.0])
        rows.append([f"generate/conc{conc}/tpot_ms",
                     tpot.mean() * 1e3, 0.0])
    rows.append(["generate/conc8/speedup_vs_serial",
                 conc_tok_s[8] / serial_tok_s, 0.0])
    if getattr(args, "compute_quant", False):
        rows += _quant_generate_rows(args, cfg, model, name, store, spec,
                                     n_req, cache_len, conc_tok_s[8])
    if getattr(args, "compute_paged", False):
        rows += _paged_generate_rows(args, cfg, model, name, store, spec,
                                     n_req, cache_len)
    return rows


def _quant_generate_rows(args, cfg, model, name, store, spec, n_req,
                         cache_len, f32_tok_s):
    """--compute-quant rows: quantized-resident serving vs the f32 path.

    Rows (name, value, derived):
      generate/quant/tok_s           conc8 aggregate tokens/s from the
                                     quantized-resident instance;
                                     derived = max slot occupancy
      generate/quant/tok_s_vs_f32    ratio vs the f32 conc8 run
                                     (gated >= 0.9: fused dequant must
                                     not tank decode throughput)
      generate/quant/resident_ratio  f32 / quant WeightCache resident
                                     bytes after one cold start each
                                     (gated >= 1.66, i.e. quant <= 0.6x
                                     f32); derived = quant bytes
      generate/quant/params_ratio    f32 / quant instance param bytes
                                     (QuantLeaf residency on-device);
                                     derived = quant param bytes
    """
    import jax
    from repro.quant import QuantLeaf
    from repro.store.store import deploy_model

    qname = f"{name}-int8"
    if not store.has_model(qname):
        deploy_model(store, model, qname, jax.random.key(0), quant="int8")

    def build(nm, cq):
        # unbounded caches so resident bytes reflect the full artifact
        return ServerlessPlatform(
            store, {nm: (lambda: (model, common.make_batch(cfg)))},
            strategy="cicada", keep_alive_s=1e9, max_instances=1,
            gen_slots=8, gen_cache_len=cache_len,
            cache_budget_bytes=0, compute_quant=cq)

    def param_bytes(tree):
        return sum(l.nbytes for l in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda l: isinstance(l, QuantLeaf)))

    rows = []
    qp = build(qname, True)
    router = qp.router(workers=8)
    q_tok_s = 0.0
    try:
        router.submit(Request(req_id=-1, model=qname,
                              gen=spec())).result()     # cold + jit warm
        inst = qp.pools[qname]._instances[0]
        inst.scheduler.reset_peaks()
        # best of two rounds: the f32 conc8 number this compares against
        # ran after two earlier concurrency levels (fully warm), so a
        # single round here would eat the remaining warmup noise
        for rnd in range(2):
            t0 = time.monotonic()
            futs = [router.submit(Request(req_id=rnd * n_req + i,
                                          model=qname, gen=spec(i)))
                    for i in range(n_req)]
            rs = [f.result() for f in futs]
            wall = time.monotonic() - t0
            q_tok_s = max(q_tok_s, sum(r.n_generated for r in rs) / wall)
    finally:
        router.shutdown()
    occ = inst.scheduler.stats()["max_occupancy"]
    q_cache = qp.cache_stats().bytes_cached
    q_params = param_bytes(inst.params)

    fp = build(name, False)
    router = fp.router(workers=1)
    try:
        router.submit(Request(req_id=0, model=name, gen=spec())).result()
    finally:
        router.shutdown()
    f_cache = fp.cache_stats().bytes_cached
    f_params = param_bytes(fp.pools[name]._instances[0].params)

    rows.append(["generate/quant/tok_s", q_tok_s, float(occ)])
    rows.append(["generate/quant/tok_s_vs_f32", q_tok_s / f32_tok_s, 0.0])
    rows.append(["generate/quant/resident_ratio", f_cache / q_cache,
                 float(q_cache)])
    rows.append(["generate/quant/params_ratio", f_params / q_params,
                 float(q_params)])
    return rows


def _paged_generate_rows(args, cfg, model, name, store, spec, n_req,
                         cache_len):
    """--compute-paged rows: KV-page serving and its prefix cache.

    Rows (name, value, derived):
      generate/paged/tok_s             conc8 aggregate tokens/s through
                                       an instance with 16-token pages
                                       (default byte budget: every
                                       slot's cache_len positions);
                                       derived = max slot occupancy
      generate/paged/prefix_ttft_ms    TTFT of a request whose 960-token
                                       prefix is already resident in
                                       the prefix cache (prefill covers
                                       only the 64-token suffix);
                                       derived = its cold twin's TTFT
      generate/paged/prefix_ttft_speedup
                                       cold-twin TTFT / prefix-hit TTFT
                                       (gated >= 2.0: the paper-regime
                                       win of skipping shared-prefix
                                       prefill); derived = cumulative
                                       prefix-hit pages
    """
    rows = []

    def build(cl, pt, *, slots=8):
        return ServerlessPlatform(
            store, {name: (lambda: (model, common.make_batch(cfg)))},
            strategy="cicada", keep_alive_s=1e9, max_instances=1,
            gen_slots=slots, gen_cache_len=cl, kv_page_tokens=pt)

    # ---- conc8 tokens/s ---------------------------------------------------
    pp = build(cache_len, 16)
    p_router = pp.router(workers=8)
    p_tok_s = 0.0
    try:
        p_router.submit(Request(req_id=-1, model=name,         # cold + jit
                                gen=spec())).result()
        inst = pp.pools[name]._instances[0]
        inst.scheduler.reset_peaks()

        def round_(router, rnd):
            t0 = time.monotonic()
            futs = [router.submit(Request(req_id=rnd * n_req + i,
                                          model=name, gen=spec(i)))
                    for i in range(n_req)]
            rs = [f.result() for f in futs]
            return sum(r.n_generated for r in rs) / \
                (time.monotonic() - t0)

        for rnd in range(2):                            # best of two
            p_tok_s = max(p_tok_s, round_(p_router, rnd))
    finally:
        p_router.shutdown()
    occ = inst.scheduler.stats()["max_occupancy"]
    rows.append(["generate/paged/tok_s", p_tok_s, float(occ)])

    # ---- prefix-cache TTFT: shared 960-token prefix, 64-token suffix ------
    pt2, n_pfx, n_sfx = 64, 960, 64
    rngp = np.random.default_rng(7)

    def pspec(prefix, seed):
        sfx = rngp.integers(0, cfg.vocab_size, (n_sfx,)).astype(np.int32)
        return GenerateSpec(prompt=np.concatenate([prefix, sfx]),
                            n_new=4, seed=seed)

    def pfx():
        return rngp.integers(0, cfg.vocab_size,
                             (n_pfx,)).astype(np.int32)

    fp = build(n_pfx + n_sfx + 16, pt2, slots=2)
    router = fp.router(workers=1)
    try:
        # warm both compile paths outside the timed pairs: full prefill
        # (cold miss) and gather + prefill-continue at off == n_pfx
        w = pfx()
        router.submit(Request(req_id=-1, model=name,
                              gen=pspec(w, 0))).result()
        router.submit(Request(req_id=-2, model=name,
                              gen=pspec(w, 0))).result()
        colds, warms = [], []
        for k in range(2):          # best of two cold/warm pairs
            prefix = pfx()
            rc = router.submit(Request(req_id=2 * k, model=name,
                                       gen=pspec(prefix, k))).result()
            rw = router.submit(Request(req_id=2 * k + 1, model=name,
                                       gen=pspec(prefix, k))).result()
            colds.append(rc.ttft_s)
            warms.append(rw.ttft_s)
        sched = fp.pools[name]._instances[0].scheduler
        hits = sched.kvpool.stats().prefix_hits
    finally:
        router.shutdown()
    cold_ms, warm_ms = min(colds) * 1e3, min(warms) * 1e3
    rows.append(["generate/paged/prefix_ttft_ms", warm_ms, cold_ms])
    rows.append(["generate/paged/prefix_ttft_speedup", cold_ms / warm_ms,
                 float(hits)])
    return rows


def slo_run(args):
    """--workload slo: open-loop SLO attainment under a 10x burst.

    The same Poisson arrival schedule (trickle phase, then a 10x burst)
    is replayed twice through benchmarks/load_gen's open loop — mixed
    generation + one-shot classes, each with a client-perceived SLO
    target measured from submit:

      noscale    bare platform; the burst's scale-out cold starts run
                 on the request path and land in p99 TTFT
      autoscale  the Autoscaler observes the trickle, pre-provisions
                 warm instances off the arrival-rate slope, and the
                 burst finds them ready

    Jit compilation is warmed (and the pool scaled back to cold)
    before each measured run, and the store is re-wrapped at
    ``--slo-bandwidth-mbps`` so a cold start costs what it costs in
    the paper's regime instead of vanishing into this host's page
    cache with smoke-size weights.

    Rows (name, value, derived):
      slo/{v}/attainment        fraction of *scheduled* requests meeting
                                their class SLO (rejected/failed = miss);
                                derived = n scheduled
      slo/{v}/ttft_p50_ms       client TTFT (queue + service first token)
      slo/{v}/ttft_p99_ms       derived = cold-served request count
      slo/{v}/oneshot_p99_ms    one-shot client latency p99; derived = n
      slo/autoscale/prewarms    off-path provisioning runs; derived =
                                live instances at drain
      slo/improvement/p99_ttft_ratio
                                noscale p99 TTFT / autoscale p99 TTFT
                                (>1: the autoscaler moved the tail);
                                derived = attainment delta
    """
    from benchmarks import load_gen as lg
    from repro.store.store import BandwidthModel, WeightStore

    rows = []
    name = args.models[0]
    cfg, model = common.get_model(name, args.quick)
    if not hasattr(model, "decode_step"):
        raise SystemExit(
            f"--workload slo needs a decoder LM, got {name!r} "
            f"({cfg.family.value}); try --models smollm-360m")
    store, root = common.deployed_store(args)
    common.ensure_deployed(store, name, args.quick)
    slow = WeightStore(root, BandwidthModel(args.slo_bandwidth_mbps, 0.2))

    n_new = args.n_new or 8
    prompt_len = args.prompt_len
    cache_len = max(64, prompt_len + n_new)
    max_inst = 4
    base_rps = 1.5
    phases = [(3.0, base_rps), (2.0, 10.0 * base_rps)]
    # between warm service (~15ms TTFT / ~1ms one-shot) and an on-path
    # cold start (~150ms+ at the slo bandwidth): warm requests pass,
    # requests that pay a cold start or deep queueing miss
    classes = [lg.LoadClass("gen", weight=0.75, gen=True, slo_s=0.075),
               lg.LoadClass("oneshot", weight=0.25, gen=False,
                            slo_s=0.075)]

    def spec(i):
        rng = np.random.default_rng(max(i, 0) + 1)
        return GenerateSpec(
            prompt=rng.integers(0, cfg.vocab_size,
                                (prompt_len,)).astype(np.int32),
            n_new=n_new, seed=max(i, 0))

    def make_batch():
        return common.make_batch(cfg)

    reports = {}
    for tag, autoscale in (
            ("noscale", None),
            # budget 0.4 rps/instance so the trickle alone justifies a
            # full pool; horizon ~ a few cold starts ahead; scale-in
            # disabled (the run is shorter than any idle window)
            ("autoscale", dict(rps_per_instance=0.4, window_s=4.0,
                               horizon_s=2.0, queue_per_instance=4,
                               idle_scale_in_s=1e9, interval_s=0.1,
                               max_prewarm_workers=3))):
        rng = np.random.default_rng(0)      # same schedule both runs
        arrivals = lg.poisson_arrivals(phases, rng)
        platform = ServerlessPlatform(
            slow, {name: (lambda: (model, common.make_batch(cfg)))},
            strategy="cicada", keep_alive_s=1e9, max_instances=max_inst,
            gen_slots=2, gen_cache_len=cache_len, autoscale=autoscale)
        router = platform.router(workers=2 * max_inst)
        try:
            # compile prefill/step/assemble outside the measured window,
            # then evict back so both variants start from a cold pool
            router.submit(Request(req_id=-1, model=name,
                                  gen=spec(-1))).result()
            router.submit(Request(req_id=-2, model=name,
                                  batch=make_batch())).result()
            for _ in range(200):
                platform.pools[name].scale_in(0)
                if platform.pool_stats()[name].live == 0:
                    break
                time.sleep(0.01)
            assert platform.pool_stats()[name].live == 0
            if platform.autoscaler is not None:
                platform.autoscaler.start()
            recs = lg.run_open_loop(router.submit, name, arrivals,
                                    classes, spec, make_batch, rng)
        finally:
            if platform.autoscaler is not None:
                platform.autoscaler.stop()
            router.shutdown()
        rep = lg.slo_report(recs, classes)
        reports[tag] = rep
        ps = platform.pool_stats()[name]
        print(f"# slo/{tag}: n={rep['n']} ok={rep['n_ok']} "
              f"cold={rep['n_cold']} prewarms={ps.prewarms} "
              f"live={ps.live} attain={rep['attainment']:.2f} "
              f"ttft_p99={rep['ttft_p99_ms'] or 0.0:.1f}ms")
        rows.append([f"slo/{tag}/attainment", rep["attainment"],
                     float(rep["n"])])
        rows.append([f"slo/{tag}/ttft_p50_ms",
                     rep["ttft_p50_ms"] or 0.0, 0.0])
        rows.append([f"slo/{tag}/ttft_p99_ms",
                     rep["ttft_p99_ms"] or 0.0, float(rep["n_cold"])])
        rows.append([f"slo/{tag}/oneshot_p99_ms",
                     rep["oneshot/p99_ms"] or 0.0,
                     float(rep["oneshot/n"])])
        if tag == "autoscale":
            rows.append(["slo/autoscale/prewarms", float(ps.prewarms),
                         float(ps.live)])
    no, au = reports["noscale"], reports["autoscale"]
    if no["ttft_p99_ms"] and au["ttft_p99_ms"]:
        rows.append(["slo/improvement/p99_ttft_ratio",
                     no["ttft_p99_ms"] / au["ttft_p99_ms"],
                     au["attainment"] - no["attainment"]])
    return rows


def cluster_run(args):
    """--workload cluster: multi-node scale-out bursts over the
    peer-exchange tier vs cluster-blind origin re-reads.

    Every platform is warmed once (jit compile) and flushed back to
    cold before its measured burst, the origin store is re-wrapped at
    ``--cluster-origin-mbps`` on a single shared channel (the slow
    pipe all nodes contend on), and the intra-cluster link runs at
    ``--cluster-bw-mbps`` with one channel per node.

    Rows (name, value, derived):
      cluster/nodes{n}/burst_ms     wall time of n simultaneous cold
                                    starts (one per node) with peer
                                    exchange on; derived = origin-store
                                    reads the burst performed (the
                                    cluster-wide single-flight should
                                    hold it at one per shard regardless
                                    of n)
      cluster/nodes{n}/origin_burst_ms
                                    the same burst with cluster-blind
                                    nodes (peer exchange off): every
                                    node re-reads the shared origin;
                                    derived = origin reads (~n per
                                    shard)
      cluster/peer_vs_origin/speedup
                                    origin_burst / burst at the largest
                                    n — the paper-regime win of moving
                                    scale-out bytes onto the cluster
                                    link; derived = that n
      cluster/second_node/zero_origin_reads
                                    1.0 when a second node's cold start
                                    of an already-landed model touched
                                    the origin store zero times;
                                    derived = its peer-read count
      cluster/second_node/load_ms   that peer-served cold start's
                                    pipeline time; derived = the
                                    leader's origin-read load_ms
    """
    from repro.cluster import ClusterPlatform
    from repro.store.store import BandwidthModel, WeightStore

    rows = []
    name = args.models[0]
    cfg, model = common.get_model(name, args.quick)
    store, root = common.deployed_store(args)
    common.ensure_deployed(store, name, args.quick)
    batch = common.make_batch(cfg)
    builders = {name: (lambda: (model, batch))}

    def build(n, peer):
        # fresh BandwidthModel per platform: no token-bucket backlog
        # leaks from one measured burst into the next
        slow = WeightStore(root, BandwidthModel(args.cluster_origin_mbps,
                                                0.2))
        return ClusterPlatform(slow, builders, n_nodes=n,
                               cluster_bw_mbps=args.cluster_bw_mbps,
                               peer_exchange=peer,
                               keep_alive_s=1e9, max_instances=1)

    def origin_count(cp):
        """Origin-store reads so far: the peer tier's counter when it
        exists, else every cache miss was an origin read (cluster-blind
        baseline)."""
        if cp.nodes[0].source is not None:
            return sum(nd.origin_reads() for nd in cp.nodes)
        return sum(nd.metrics.counter("weight_cache/misses").value
                   for nd in cp.nodes)

    def burst(cp):
        """Simultaneous cold start on every node (jit warmed, cluster
        flushed): wall seconds, responses, origin-read count."""
        router = cp.router(workers_per_node=2)
        try:
            # warm EVERY node's instance (each container jit-compiles
            # its own forward) outside the timed window, then flush —
            # eviction keeps the instance objects and their compiles
            for i, nd in enumerate(cp.nodes):
                router.submit_to(nd.node_id,
                                 Request(req_id=-(i + 1), model=name,
                                         batch=batch)).result()
            cp.flush()
            o0 = origin_count(cp)
            t0 = time.monotonic()
            futs = [router.submit_to(nd.node_id,
                                     Request(req_id=i, model=name,
                                             batch=batch))
                    for i, nd in enumerate(cp.nodes)]
            rs = [f.result() for f in futs]
            wall = time.monotonic() - t0
        finally:
            router.shutdown()
        origin = origin_count(cp) - o0
        assert all(r.cold for r in rs), "burst must be all cold starts"
        return wall, rs, origin

    n_max = max(args.nodes)
    peer_wall = {}
    for n in sorted(args.nodes):
        wall, _, origin = burst(build(n, True))
        peer_wall[n] = wall
        rows.append([f"cluster/nodes{n}/burst_ms", wall * 1e3,
                     float(origin)])
    wall, _, origin = burst(build(n_max, False))
    rows.append([f"cluster/nodes{n_max}/origin_burst_ms", wall * 1e3,
                 float(origin)])
    rows.append(["cluster/peer_vs_origin/speedup",
                 wall / peer_wall[n_max], float(n_max)])

    # ---- second node cold-starts an already-landed model ------------------
    cp = build(2, True)
    router = cp.router(workers_per_node=2)
    try:
        for i, nd in enumerate(cp.nodes):
            router.submit_to(nd.node_id,
                             Request(req_id=-(i + 1), model=name,
                                     batch=batch)).result()
        cp.flush()
        r0 = router.submit_to("node0", Request(req_id=0, model=name,
                                               batch=batch)).result()
        r1 = router.submit_to("node1", Request(req_id=1, model=name,
                                               batch=batch)).result()
    finally:
        router.shutdown()
    second = cp.node("node1")
    assert r0.cold and r1.cold
    rows.append(["cluster/second_node/zero_origin_reads",
                 1.0 if second.origin_reads() == 0 else 0.0,
                 second.peer_reads()])
    rows.append(["cluster/second_node/load_ms", r1.load_s * 1e3,
                 r0.load_s * 1e3])
    return rows


def _mesh_tag(args) -> str:
    """Row prefix AND json bench name of the --mesh sweep (one source
    so the artifact's bench field can't drift from its rows)."""
    return "sharded_int8" if getattr(args, "quant", None) == "int8" \
        else "sharded"


def mesh_run(args):
    """--mesh: shard-granular cold starts on simulated meshes of
    1 / 2 / 4 devices (``--quant int8``: from a quantized deployment,
    with per-shard dequant on the placement lanes).

    Every mesh device brings its own ``--bandwidth-mbps`` store channel
    (``BandwidthModel(channels=n)``) — the λScale / HydraServe regime
    where aggregate load bandwidth scales with workers — so the
    critical-path load time of a cicada cold start should fall ~n-fold.

    Rows (name, load_ms, derived):
      sharded/mesh{n}/load_ms        end-to-end cold-start pipeline time,
                                     min of 3 warmed loads (this host's
                                     CPU-count dwarfs the simulated
                                     device count, so single-shot walls
                                     carry scheduler noise); derived =
                                     that load's retrieval-window ms
                                     (first R start -> last R end)
      sharded/mesh4_vs_mesh1/speedup load-time ratio (monotonicity +
                                     the >=2x acceptance row)
    """
    import dataclasses
    import tempfile

    import jax

    from repro.core import ColdStartEngine
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer
    from repro.models.api import get_config
    from repro.store.store import BandwidthModel, WeightStore, deploy_model

    quant = getattr(args, "quant", None)
    tag = _mesh_tag(args)
    # a mid-size LM (~155 MB f32 / ~40 MB int8) so retrieval dominates
    # the pipeline at 200 MB/s — every sharded axis divides 4 (no
    # replication fallback) and d_ff/4 int8 column runs clear the
    # byte-range floor (1024 B)
    cfg = dataclasses.replace(
        get_config("smollm-360m", smoke=True), name=f"{tag}-bench",
        n_layers=8, d_model=384, n_heads=4, n_kv_heads=4, d_ff=4096,
        vocab_size=12288)
    model = transformer.build(cfg)
    root = tempfile.mkdtemp(prefix=f"cicada-{tag}-bench-")
    deploy_model(WeightStore(root), model, cfg.name, jax.random.key(0),
                 quant=quant)
    batch = common.make_batch(cfg)

    rows = []
    load_ms = {}
    for n in (1, 2, 4):
        if n > jax.device_count():
            print(f"# mesh={n}: only {jax.device_count()} devices, "
                  f"skipping (set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count=4)")
            continue
        store = WeightStore(root, BandwidthModel(args.bandwidth_mbps, 0.2,
                                                 channels=n))
        mesh = make_serving_mesh((1, n)) if n > 1 else None
        eng = ColdStartEngine(model, cfg.name, store, strategy="cicada",
                              mesh=mesh)
        eng.warmup(batch)
        eng.load(batch)                   # warm assemble jit / put paths
        best = None
        for _ in range(3):
            res = eng.load(batch)
            if best is None or res.trace.total_time() < \
                    best.trace.total_time():
                best = res
        R = [e for e in best.trace.events if e.stage == "R"]
        r_window = max(e.t_end for e in R) - min(e.t_start for e in R)
        load_ms[n] = best.trace.total_time() * 1e3
        rows.append([f"{tag}/mesh{n}/load_ms", load_ms[n],
                     r_window * 1e3])
    if 1 in load_ms and 4 in load_ms:
        rows.append([f"{tag}/mesh4_vs_mesh1/speedup",
                     load_ms[1] / load_ms[4], 0.0])
    return rows


def run(args=None, n_invocations: int = 24, strategies=("pisel", "cicada"),
        concurrencies=(1, 4)):
    args = args or common.std_parser(models=["resnet50"]).parse_args([])
    n_invocations = getattr(args, "invocations", None) or n_invocations
    if getattr(args, "mesh", False):
        rows = mesh_run(args)
        common.print_csv(["name", "load_ms", "derived"], rows)
        _write_json(args, rows, _mesh_tag(args))
        return rows
    if getattr(args, "workload", "trace") == "generate":
        rows = generate_run(args)
        common.print_csv(["name", "value", "derived"], rows)
        _write_json(args, rows, "generate")
        return rows
    if getattr(args, "workload", "trace") == "slo":
        rows = slo_run(args)
        common.print_csv(["name", "value", "derived"], rows)
        _write_json(args, rows, "slo")
        return rows
    if getattr(args, "workload", "trace") == "cluster":
        rows = cluster_run(args)
        common.print_csv(["name", "value", "derived"], rows)
        _write_json(args, rows, "cluster")
        return rows
    rows = []
    store, _ = common.deployed_store(args)
    models = common.model_list(args)
    for name in models:
        common.ensure_deployed(store, name, args.quick)
    trace = azure_like_trace(duration_s=240.0, n_invocations=n_invocations,
                             models=models, seed=0)
    print(f"# trace: {summarize(trace)}")
    for strat in strategies:
        rs, _ = _replay(store, models, args, trace, strat)
        lat = np.array([r.latency_s for r in rs])
        cold = np.array([r.cold for r in rs])
        rows.append([f"trace/{strat}/mean", lat.mean() * 1e6,
                     float(cold.mean())])
        rows.append([f"trace/{strat}/p99",
                     np.percentile(lat, 99) * 1e6, 0.0])
        if cold.any():
            rows.append([f"trace/{strat}/cold_mean",
                         lat[cold].mean() * 1e6, int(cold.sum())])
    # concurrency sweep: same trace, Router worker pool + pool scale-out
    for conc in concurrencies:
        if conc <= 1:
            continue
        rs, platform = _replay(store, models, args, trace, "cicada",
                               concurrency=conc, max_instances=conc)
        lat = np.array([r.latency_s for r in rs])
        q = np.array([r.queue_s for r in rs])
        st = platform.last_router_stats
        rows.append([f"trace/cicada/conc{conc}/mean", lat.mean() * 1e6,
                     float(st.max_in_flight)])
        rows.append([f"trace/cicada/conc{conc}/queue_mean",
                     q.mean() * 1e6, float(st.max_queue_depth)])
    # scale-out sweep: node-local WeightCache, cold vs warm-cache
    rows.extend(scaleout_sweep(store, models, args))
    common.print_csv(["name", "us_per_call", "derived"], rows)
    _write_json(args, rows, "trace")
    return rows


def _write_json(args, rows, bench: str):
    json_out = getattr(args, "json_out", None)
    if json_out:
        header = {"generate": ["name", "value", "derived"],
                  "slo": ["name", "value", "derived"],
                  "cluster": ["name", "value", "derived"],
                  "sharded": ["name", "load_ms", "derived"],
                  "sharded_int8": ["name", "load_ms", "derived"]}.get(
            bench, ["name", "us_per_call", "derived"])
        obj = {"bench": bench, "header": header,
               "rows": [[n, float(v), float(d)] for n, v, d in rows]}
        # catch a malformed artifact at the producer, not in CI
        schema.validate(obj, source=json_out)
        with open(json_out, "w") as f:
            json.dump(obj, f, indent=2)
        print(f"# wrote {json_out}")


def main(argv=None):
    ap = common.std_parser(models=["resnet50"])
    ap.add_argument("--invocations", type=int, default=None,
                    help="trace length (default 24)")
    ap.add_argument("--json-out", default=None,
                    help="also write rows as JSON (CI artifact)")
    ap.add_argument("--workload", default="trace",
                    choices=["trace", "generate", "slo", "cluster"],
                    help="trace: one-shot replay benches (default); "
                         "generate: continuous-batching TTFT/TPOT/"
                         "tokens-per-second benches (LM model required, "
                         "e.g. --models smollm-360m); slo: open-loop "
                         "10x-burst SLO attainment, autoscaler on vs "
                         "off (LM model required); cluster: multi-node "
                         "scale-out bursts, peer shard exchange vs "
                         "origin re-reads")
    ap.add_argument("--nodes", type=int, nargs="+", default=[1, 2, 4],
                    help="--workload cluster: node counts to sweep")
    ap.add_argument("--cluster-bw-mbps", type=float, default=1000.0,
                    help="--workload cluster: intra-cluster link "
                         "bandwidth (one channel per node)")
    ap.add_argument("--cluster-origin-mbps", type=float, default=20.0,
                    help="--workload cluster: shared origin-store "
                         "bandwidth (single channel: the slow pipe)")
    ap.add_argument("--slo-bandwidth-mbps", type=float, default=5.0,
                    help="--workload slo: simulated store bandwidth for "
                         "the SLO runs (low, so a cold start has a "
                         "realistic cost relative to smoke-size "
                         "weights)")
    ap.add_argument("--n-new", type=int, default=None,
                    help="tokens per generation request "
                         "(default: 16 quick / 32 full)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-requests", type=int, default=None,
                    help="generation requests per concurrency level "
                         "(default: 8 quick / 16 full)")
    ap.add_argument("--mesh", action="store_true",
                    help="shard-granular cold-start sweep over device "
                         "meshes 1/2/4 (one store channel per device); "
                         "emits the BENCH_sharded.json rows")
    ap.add_argument("--quant", default=None, choices=["int8"],
                    help="deploy the --mesh sweep's model quantized: "
                         "shard streams carry value+scale slices and "
                         "placement lanes run the per-shard dequant")
    ap.add_argument("--compute-quant", action="store_true",
                    help="--workload generate: add quantized-resident "
                         "serving rows — an int8 deployment served with "
                         "compute_quant (QuantLeaf params + fused-"
                         "dequant quant_matmul), reporting tokens/s vs "
                         "f32 and the resident-bytes ratio")
    ap.add_argument("--compute-paged", action="store_true",
                    help="--workload generate: add KV-page serving "
                         "rows — conc8 tokens/s with 16-token pages and "
                         "the prefix-cache TTFT speedup on a shared "
                         "960-token prefix")
    ap.add_argument("--pallas", default=None,
                    choices=["auto", "pallas", "interpret", "ref"],
                    help="force the kernel dispatch registry (default: "
                         "capability-probed auto)")
    args = ap.parse_args(argv)
    if args.pallas:
        from repro.kernels import ops
        ops.set_mode(None if args.pallas == "auto" else args.pallas)
    return run(args)


if __name__ == "__main__":
    main()
