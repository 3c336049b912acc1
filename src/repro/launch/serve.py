"""Serving launcher: trace-driven serverless inference with Cicada.

``python -m repro.launch.serve --strategy cicada --models smollm-360m``

Deploys the requested models to a local weight store (with a simulated
storage device so the I/O phase is visible), generates an Azure-like
invocation trace, replays it through the ServerlessPlatform and prints
per-strategy latency / utilization statistics.

``--workload generate --n-new 16`` replays the same trace as
*generation* requests: each invocation decodes n-new tokens through the
instances' continuous-batching DecodeSchedulers, and the report adds
TTFT / TPOT / tokens-per-second.

``--mesh 4`` streams weights shard-granularly onto a 4-way model-
parallel device mesh (one byte-range retrieval stream per device, each
on its own simulated store channel) and serves warm requests from the
mesh-sharded params.  On CPU the devices are simulated — the flag below
is set automatically when unset.

``--pallas {auto,pallas,interpret,ref}`` forces the kernel dispatch
registry for every jitted serving path (default: auto — capability-
probed per kernel; see :mod:`repro.kernels.ops`).

``--nodes N`` serves the trace from an N-node cluster
(:mod:`repro.cluster`): a locality-aware front-end router places each
invocation on the node already warm / cache-resident for the model,
and scale-out cold starts stream weights from peer nodes over the
intra-cluster link (``--cluster-bw-mbps``) instead of re-reading the
shared origin store — at most one origin read per shard, cluster-wide.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

# Must precede the jax import: jax locks the device count on first init.
# A CPU run of `--mesh N` needs N simulated host devices.
if "XLA_FLAGS" not in os.environ:
    _n = 0
    for _i, _a in enumerate(sys.argv):
        try:
            if _a == "--mesh":
                _n = int(sys.argv[_i + 1])
            elif _a.startswith("--mesh="):
                _n = int(_a.split("=", 1)[1])
        except (IndexError, ValueError):
            _n = 4
    if _n > 1:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={_n}"

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.models import transformer
from repro.models.api import get_config
from repro.serving.api import GenerateSpec
from repro.serving.engine import ServerlessPlatform
from repro.serving.trace import azure_like_trace, summarize
from repro.store.store import BandwidthModel, WeightStore, deploy_model


def example_batch(cfg, seq: int = 32):
    rng = np.random.default_rng(0)
    if cfg.family.value == "vision":
        return {"image": jnp.asarray(
            rng.standard_normal((1, 3, cfg.img_res, cfg.img_res)),
            jnp.float32)}
    if cfg.family.value == "audio":
        return {"frames": jnp.asarray(
            rng.standard_normal((1, seq, cfg.frontend_dim)),
            jnp.bfloat16)}
    if cfg.family.value == "vlm":
        n_img = min(8, seq // 2)
        return {"tokens": jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (1, seq - n_img)),
                    jnp.int32),
                "img": jnp.asarray(
                    rng.standard_normal((1, n_img, cfg.frontend_dim)),
                    jnp.bfloat16)}
    return {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, seq)), jnp.int32)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+", default=["smollm-360m"])
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--strategy", default="cicada",
                    choices=["traditional", "pisel", "mini", "preload",
                             "cicada"])
    ap.add_argument("--invocations", type=int, default=40)
    ap.add_argument("--duration", type=float, default=600.0)
    ap.add_argument("--keep-alive", type=float, default=30.0)
    ap.add_argument("--concurrency", type=int, default=1,
                    help="router workers / max in-flight invocations")
    ap.add_argument("--max-instances", type=int, default=1,
                    help="instance-pool scale-out limit per model")
    ap.add_argument("--workload", default="oneshot",
                    choices=["oneshot", "generate"],
                    help="oneshot: batch->logits forwards (seed "
                         "semantics); generate: multi-token decode "
                         "through the continuous-batching scheduler")
    ap.add_argument("--n-new", type=int, default=16,
                    help="tokens to generate per invocation "
                         "(--workload generate)")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length for generation invocations")
    ap.add_argument("--gen-slots", type=int, default=8,
                    help="decode-scheduler slots per instance "
                         "(max concurrent generations batching)")
    ap.add_argument("--gen-cache-len", type=int, default=256,
                    help="most positions (prompt + generated) one "
                         "generation request may hold")
    ap.add_argument("--kv-page-tokens", type=int, default=16,
                    metavar="PT",
                    help="decode KV page size: full-attention K/V lives "
                         "in a shared refcounted pool of PT-token pages "
                         "(page-budget admission + prefix caching)")
    ap.add_argument("--kv-budget-mb", type=float, default=None,
                    help="device byte budget for the KV page pool across "
                         "all attention layers (default: gen-slots x "
                         "gen-cache-len tokens' worth)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 = sampled generation")
    ap.add_argument("--cache-budget-mb", type=float, default=None,
                    help="enable the node-local shared WeightCache with "
                         "this byte budget (0 = unbounded; default: no "
                         "cache)")
    ap.add_argument("--mesh", type=int, default=1,
                    help="model-parallel mesh width: stream weights "
                         "shard-granularly onto (1, N) devices and "
                         "serve warm requests sharded (1 = seed path)")
    ap.add_argument("--pallas", default=None,
                    choices=["auto", "pallas", "interpret", "ref"],
                    help="force the kernel dispatch registry for every "
                         "jitted serving path (default: capability-"
                         "probed auto; see repro.kernels.ops)")
    ap.add_argument("--compute-quant", action="store_true",
                    help="serve int8 weights in place: deploy models "
                         "quantized (int8 values + per-column scales), "
                         "keep them quantized-resident across cold "
                         "starts (~quarter the f32 bytes) and run "
                         "weight matmuls through the fused-dequant "
                         "quant_matmul kernel (single device only)")
    ap.add_argument("--nodes", type=int, default=1,
                    help="serve from an N-node cluster (repro.cluster): "
                         "locality-aware routing + peer-to-peer shard "
                         "exchange (1 = single-node platform)")
    ap.add_argument("--cluster-bw-mbps", type=float, default=1000.0,
                    help="--nodes N: intra-cluster link bandwidth, one "
                         "channel per node (0 = unthrottled)")
    ap.add_argument("--bandwidth-mbps", type=float, default=400.0,
                    help="simulated store bandwidth per channel; with "
                         "--mesh N the store exposes N channels (one "
                         "independent link per device)")
    ap.add_argument("--store", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the platform's metrics snapshot (the "
                         "scrapeable counter/gauge/histogram JSON) to "
                         "this path after the replay")
    ap.add_argument("--autoscale", action="store_true",
                    help="attach the SLO autoscaler: pre-provision warm "
                         "instances on arrival-rate slope / queue "
                         "depth, scale-in on idle")
    ap.add_argument("--rps-per-instance", type=float, default=2.0,
                    help="--autoscale: arrival rate one warm instance "
                         "is budgeted to absorb")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.pallas:
        from repro.kernels import ops
        ops.set_mode(None if args.pallas == "auto" else args.pallas)

    if args.compute_quant and (args.mesh > 1 or args.nodes > 1):
        raise SystemExit("--compute-quant serves int8 leaves in place on "
                         "a single device; not supported with --mesh/"
                         "--nodes")

    store_dir = args.store or tempfile.mkdtemp(prefix="cicada-store-")
    store = WeightStore(store_dir,
                        BandwidthModel(args.bandwidth_mbps, 0.2,
                                       channels=max(1, args.mesh)))

    builders = {}
    for name in args.models:
        cfg = get_config(name, smoke=args.smoke)
        model = transformer.build(cfg)
        if args.workload == "generate" and not hasattr(model,
                                                       "decode_step"):
            raise SystemExit(
                f"--workload generate needs decoder LMs, got {name!r} "
                f"({cfg.family.value}); try --models smollm-360m")
        if not store.has_model(name):
            print(f"deploying {name} "
                  f"({cfg.param_count() / 1e6:.1f}M params"
                  f"{', int8' if args.compute_quant else ''}) ...")
            deploy_model(store, model, name, jax.random.key(args.seed),
                         quant="int8" if args.compute_quant else None)
        builders[name] = (lambda m=model, c=cfg:
                          (m, example_batch(c)))

    trace = azure_like_trace(duration_s=args.duration,
                             n_invocations=args.invocations,
                             models=args.models, seed=args.seed)
    print("trace:", summarize(trace))

    cache_budget = None if args.cache_budget_mb is None \
        else int(args.cache_budget_mb * 1e6)
    kv_budget = None if args.kv_budget_mb is None \
        else int(args.kv_budget_mb * 1e6)
    is_cluster = args.nodes > 1
    if is_cluster:
        if args.autoscale:
            raise SystemExit("--autoscale is a per-node policy; not "
                             "supported with --nodes > 1")
        from repro.cluster import ClusterPlatform
        # the peer tier requires per-node caches: default unbounded
        platform = ClusterPlatform(
            store, builders, n_nodes=args.nodes,
            cluster_bw_mbps=args.cluster_bw_mbps,
            cache_budget_bytes=0 if cache_budget is None else cache_budget,
            strategy=args.strategy, keep_alive_s=args.keep_alive,
            max_instances=args.max_instances, gen_slots=args.gen_slots,
            gen_cache_len=args.gen_cache_len,
            kv_page_tokens=args.kv_page_tokens, kv_budget_bytes=kv_budget,
            mesh_shape=(1, args.mesh) if args.mesh > 1 else None)
    else:
        platform = ServerlessPlatform(
            store, builders, strategy=args.strategy,
            keep_alive_s=args.keep_alive,
            max_instances=args.max_instances,
            cache_budget_bytes=cache_budget,
            gen_slots=args.gen_slots,
            gen_cache_len=args.gen_cache_len,
            kv_page_tokens=args.kv_page_tokens,
            kv_budget_bytes=kv_budget,
            mesh_shape=(1, args.mesh) if args.mesh > 1 else None,
            compute_quant=args.compute_quant,
            autoscale=dict(rps_per_instance=args.rps_per_instance)
            if args.autoscale else None)
        if platform.autoscaler is not None:
            platform.autoscaler.start()

    def make_batch(name):
        return example_batch(get_config(name, smoke=args.smoke))

    make_spec = None
    if args.workload == "generate":
        rng = np.random.default_rng(args.seed)

        def make_spec(name):
            cfg = get_config(name, smoke=args.smoke)
            return GenerateSpec(
                prompt=rng.integers(0, cfg.vocab_size,
                                    (args.prompt_len,)).astype(np.int32),
                n_new=args.n_new, temperature=args.temperature,
                seed=args.seed)

    responses = platform.run_trace(trace, make_batch,
                                   concurrency=args.concurrency,
                                   make_spec=make_spec)
    lat = np.array([r.latency_s for r in responses])
    cold = np.array([r.cold for r in responses])
    print(f"strategy={args.strategy}  n={len(responses)}  "
          f"cold={cold.sum()} ({cold.mean():.0%})  "
          f"concurrency={args.concurrency}")
    print(f"latency: mean={lat.mean() * 1e3:.1f}ms "
          f"p50={np.percentile(lat, 50) * 1e3:.1f}ms "
          f"p99={np.percentile(lat, 99) * 1e3:.1f}ms")
    if cold.any():
        cl = lat[cold]
        ut = np.array([r.utilization for r in responses])[cold]
        print(f"cold-start: mean={cl.mean() * 1e3:.1f}ms "
              f"pipeline-util={ut.mean():.1%}")
    if args.workload == "generate":
        ttft = np.array([r.ttft_s for r in responses])
        tpot = np.concatenate([r.tpot_s for r in responses
                               if r.tpot_s]) if any(
            r.tpot_s for r in responses) else np.array([0.0])
        n_tok = sum(r.n_generated for r in responses)
        span = max(r.t_done for r in responses) - \
            min(r.t_arrival for r in responses)
        print(f"generation: n_new={args.n_new}  total-tokens={n_tok}  "
              f"tokens/s={n_tok / max(span, 1e-9):.1f}")
        print(f"TTFT: p50={np.percentile(ttft, 50) * 1e3:.1f}ms "
              f"p99={np.percentile(ttft, 99) * 1e3:.1f}ms   "
              f"TPOT: mean={tpot.mean() * 1e3:.2f}ms")
        if cold.any():
            ct = ttft[cold]
            cl2 = np.array([r.load_s for r in responses])[cold]
            print(f"cold TTFT: mean={ct.mean() * 1e3:.1f}ms "
                  f"(load {cl2.mean() * 1e3:.1f}ms — first token "
                  f"in-pipeline: {bool((ct < cl2).all())})")
    if args.concurrency > 1 and not is_cluster:
        q = np.array([r.queue_s for r in responses])
        rs = platform.last_router_stats
        print(f"queueing: mean={q.mean() * 1e3:.1f}ms "
              f"max={q.max() * 1e3:.1f}ms  "
              f"max-in-flight={rs.max_in_flight}")
    if is_cluster:
        served = np.array([r.node for r in responses])
        for nd in platform.nodes:
            ps = nd.platform.pool_stats()
            print(f"node[{nd.node_id}]: "
                  f"served={int((served == nd.node_id).sum())} "
                  f"cold={sum(p.cold_starts for p in ps.values())} "
                  f"warm={sum(p.warm_hits for p in ps.values())} "
                  f"origin-reads={nd.origin_reads():.0f} "
                  f"peer-reads={nd.peer_reads():.0f}")
        snap = platform.cluster_snapshot()
        agg = snap["cluster"]["counters"]
        print(f"cluster: origin-reads="
              f"{agg.get('cluster/origin_reads', 0):.0f} "
              f"peer-reads={agg.get('cluster/peer_reads', 0):.0f} "
              f"peer-bytes={agg.get('cluster/peer_bytes', 0) / 1e6:.1f}MB")
        pl = snap["placement"]
        print(f"placement: models={pl['models']} "
              f"origin-elections={pl['origin_elections']} "
              f"peer-referrals={pl['peer_referrals']}")
    else:
        for name, ps in platform.pool_stats().items():
            print(f"pool[{name}]: instances={ps.size} live={ps.live} "
                  f"cold={ps.cold_starts} warm={ps.warm_hits} "
                  f"evictions={ps.evictions}")
        cs = platform.cache_stats()
        if cs is not None:
            print(f"weight-cache: hits={cs.hits} misses={cs.misses} "
                  f"deduped-reads={cs.waits} evictions={cs.evictions} "
                  f"resident={cs.bytes_cached / 1e6:.1f}MB "
                  f"hit-rate={cs.hit_rate:.0%}")
        if platform.autoscaler is not None:
            platform.autoscaler.stop()
    if args.metrics_out:
        import json
        snap = platform.cluster_snapshot() if is_cluster \
            else platform.metrics_snapshot()
        with open(args.metrics_out, "w") as f:
            json.dump(snap, f, indent=2)
        if is_cluster:
            print(f"cluster snapshot -> {args.metrics_out} "
                  f"({snap['n_nodes']} nodes)")
        else:
            print(f"metrics snapshot -> {args.metrics_out} "
                  f"({len(snap['counters'])} counters, "
                  f"{len(snap['gauges'])} gauges, "
                  f"{len(snap['histograms'])} histograms)")
    return responses


if __name__ == "__main__":
    main()
