"""ColdStartEngine: request -> live model, through the paper's pipeline.

Three execution units run as threads (exactly the paper's decomposition,
as :class:`~repro.core.units.PipelineUnit` objects on one event-driven
:class:`~repro.core.units.PipelineRuntime`):

  * **Layer unit** — constructs unit structures in order (MiniLoader or
    PISeL-faithful numerical init); under a mesh every leaf's
    NamedSharding is resolved here, so the structure handed downstream
    is already the sharded layout;
  * **Weight unit** — applies retrieved weights.  Under the
    WeightDecoupler, retrieval streams were issued at request arrival on
    an I/O pool and application is out-of-order; under PISeL, retrieval
    is fused into this unit and strictly ordered after L_i;
  * **Compute unit** — executes layer i's forward as soon as its weights
    are applied (and layer i-1 executed): the triggering request is
    answered *while the model is still loading*.

**Shard-granular cold starts** (``mesh=`` + ``rules=``): the unit of
pipelined retrieval becomes a *(layer-unit, shard)* pair — one stream
per mesh device, each reading only the byte ranges its device owns and
committing them to that device the moment they land (see
:mod:`repro.core.shards`).  The pipeline's compute units still run the
triggering request on the default device from the host-merged leaves —
numerically *identical* to the single-device path (sharded collectives
never touch the first request's logits) — while the steady-state
(scan-stacked) parameters are assembled **on the mesh** from the
already-committed shards and handed to the serving engine for warm
tensor-parallel requests.  A mesh of one device degenerates to the
seed's unit-granular path exactly.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import metrics as metrics_mod
from repro.core import miniloader
from repro.core.decoupler import ShardSource, WeightDecoupler
from repro.core.pipeline import PipelineTrace
from repro.core.scheduler import PriorityAwareScheduler
from repro.core.shards import ShardedUnitData, UnitShardPlan, plan_unit
from repro.core.strategies import Strategy, get_strategy
from repro.core.units import (OUTPUT, SHARDED, PipelineContext,
                              PipelineRuntime, PipelineState, standard_units)
from repro.distributed.sharding import (ShardingRules, leaf_specs,
                                        param_specs, serve_rules)
from repro.kernels import ops
from repro.quant import QuantLeaf
from repro.store.cache import WeightCache
from repro.store.store import WeightStore, leaf_path_name, unflatten_unit

PyTree = Any


@dataclasses.dataclass
class LoadResult:
    logits: Optional[jax.Array]  # first-request output (computed in-pipeline)
    params: Optional[PyTree]     # assembled steady-state parameters (on the
                                 # mesh, sharded, when the engine has one);
                                 # both None once the instance is evicted
    trace: PipelineTrace
    strategy: str


def _write(stacked: PyTree, unit: PyTree, i) -> PyTree:
    return jax.tree.map(
        lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x, i, 0),
        stacked, unit)


# the stacked buffers are donated, so each write updates them in place
_write_unit = jax.jit(_write, donate_argnums=0)
_read_unit = jax.jit(lambda stacked, i: jax.tree.map(lambda s: s[i],
                                                     stacked))


class Placement:
    """The steady-state params of one single-device load, filled in as
    the load goes.

    A block unit is written into its index of the scan-stacked params by
    a donated in-place update, keyed by index; each slot's stacked
    buffers are allocated once, at its first unit.  Whole units (embed,
    final, tail blocks) are kept as they are.  So a load holds its
    layers once, plus the applied units that E has not yet run, never a
    second stacked copy.  The pipeline places a unit once E has run it:
    the unit and the previous write are on the device by then, so the
    write, dispatched and not awaited, starts at once (placed as soon as
    A had applied it, the write held A up until the unit's host
    transfer landed).
    """

    def __init__(self, model):
        self.model = model
        self.whole: Dict[str, PyTree] = {}
        self.stacked: Dict[str, PyTree] = {}
        self._count = collections.Counter(
            at[0] for at in map(model.stack_index, model.unit_names())
            if at is not None)

    def place(self, unit: str, tree: PyTree):
        at = self.model.stack_index(unit)
        if at is None:
            self.whole[unit] = tree
            return
        slot, i = at
        with jax.profiler.TraceAnnotation("coldstart.place", unit=unit):
            if slot not in self.stacked:
                n = self._count[slot]
                self.stacked[slot] = jax.tree.map(
                    lambda x: jnp.zeros((n,) + x.shape, x.dtype), tree)
            self.stacked[slot] = _write_unit(self.stacked[slot], tree, i)

    def view(self, unit: str) -> PyTree:
        """Unit ``unit``'s params as placed: for a block unit, its slice
        of the stacked params (a copy of that one unit)."""
        at = self.model.stack_index(unit)
        if at is None:
            return self.whole[unit]
        slot, i = at
        return _read_unit(self.stacked[slot], i)

    def params(self) -> PyTree:
        return self.model.assemble(self.whole, self.stacked)


class ColdStartEngine:
    def __init__(self, model, model_name: str, store: WeightStore, *,
                 strategy: str = "cicada", io_workers: int = 4,
                 chunk_bytes: int = 1 << 20,
                 apply_dtype=None, compute_quant: bool = False,
                 cache: Optional[WeightCache] = None,
                 mesh=None, rules: Optional[ShardingRules] = None,
                 metrics: Optional[metrics_mod.MetricsRegistry] = None,
                 source: Optional[ShardSource] = None):
        """apply_dtype: cast weights to this dtype at application time
        (None -> keep stored dtype).

        compute_quant: keep int8 extents *resident* — application skips
        the ``weight_transform`` dequant and builds
        :class:`~repro.quant.QuantLeaf` (int8 values + scale) leaves, so
        params charge ~quarter the f32 bytes and forward passes dispatch
        through the fused-dequant ``quant_matmul`` kernel.  Leaves the
        store serves as plain floats (norms, gates, 1-D vectors) are
        untouched.  Single-device serving only.

        cache: node-local shared WeightCache — decoupled retrieval
        streams consult it before issuing I/O, so scale-out cold starts
        of the same model single-flight every store read (per shard,
        under a mesh).

        source: where cache-missing streams read their bytes (default:
        the origin store) — a cluster node passes its peer-exchange
        tier here so cold starts of already-landed models stream over
        the intra-cluster link instead (requires a cache).

        mesh/rules: shard-granular cold start — retrieval fans out into
        one stream per mesh device and the assembled params live on the
        mesh as NamedSharding arrays.  rules defaults to
        ``serve_rules()``; a 1-device mesh degenerates to the seed
        path."""
        self.model = model
        self.model_name = model_name
        self.store = store
        self.strategy: Strategy = get_strategy(strategy)
        self.io_workers = io_workers
        self.chunk_bytes = chunk_bytes
        self.apply_dtype = apply_dtype
        self.cache = cache
        self.source = source
        self.metrics = metrics_mod.resolve(metrics)
        if mesh is not None and mesh.size <= 1:
            mesh = None                    # degenerate: exact seed path
        if compute_quant and mesh is not None:
            raise ValueError(
                "compute_quant serves int8 leaves in place on a single "
                "device; mesh-sharded quantized residency is not "
                "supported (shard plans describe the dequantized layout)")
        self.compute_quant = compute_quant
        self.mesh = mesh
        self.rules = (rules if rules is not None else serve_rules()) \
            if mesh is not None else None
        self._jit_apply: Dict[str, Any] = {}
        self._shard_plans: Dict[str, UnitShardPlan] = {}
        self._unit_specs: Dict[str, Dict[str, Any]] = {}
        self._assemble_jit = None

    # -------------------------------------------------------------- helpers
    def _apply_fn(self, unit: str):
        if unit not in self._jit_apply:
            model = self.model
            self._jit_apply[unit] = jax.jit(
                lambda p, s, _u=unit: model.unit_apply(_u, p, s))
        return self._jit_apply[unit]

    def warmup(self, batch: Dict[str, jax.Array]):
        """Pre-compile per-unit forwards (deploy-time step, like a
        serverless snapshot of compiled code) so first-request E_i
        timings measure execution, not XLA compilation."""
        names = self.model.unit_names()
        keys = jax.random.split(jax.random.key(0), len(names))
        state: Dict[str, Any] = {"batch": batch}
        placement = self._placement()       # compiles the in-place write
        for name, k in zip(names, keys):
            self.model.abstract_unit(name)   # precompute static structure
            p = self.model.init_unit(name, k)
            state = self._apply_fn(name)(p, state)
            if placement is not None:
                placement.place(name, p)
        jax.block_until_ready(state["logits"])

    def _plan(self, unit: str) -> UnitShardPlan:
        """Static per-unit shard plan (cached across loads)."""
        if unit not in self._shard_plans:
            self._shard_plans[unit] = plan_unit(
                self.store, self.model_name, unit,
                self.model.abstract_unit(unit), self.mesh, self.rules,
                apply_dtype=self.apply_dtype)
        return self._shard_plans[unit]

    def _specs(self, unit: str) -> Dict[str, Any]:
        if unit not in self._unit_specs:
            self._unit_specs[unit] = leaf_specs(
                self.model.abstract_unit(unit), self.mesh, self.rules)
        return self._unit_specs[unit]

    def _apply_leaves(self, unit: str, abstract: PyTree, leaves,
                      prefetched=None) -> PyTree:
        """The weight-application compute phase: dequant/cast (fused
        ``weight_transform`` kernel) + device placement (one batched
        transfer per unit).

        prefetched: {leaf: default-device array} already placed — and,
        for dequant/cast leaves, already transformed — by the shard
        committer's placement lane; those leaves skip the transfer (and
        the transform) here and A only waits on them."""
        flat = {}
        put_names, put_arrs = [], []
        qnames, qvals, qscales = [], [], []
        for name, (arr, scale) in leaves.items():
            if prefetched is not None and name in prefetched:
                flat[name] = prefetched[name]
            elif scale is not None and self.compute_quant:
                # quantized residency: place the int8 values (at the
                # logical leaf shape) + scale, skip weight_transform
                qnames.append(name)
                qvals.append(np.asarray(arr).reshape(
                    self._leaf_shape(abstract, name)))
                qscales.append(np.asarray(scale))
            elif scale is not None:                    # int8 extent
                out_dt = self.apply_dtype or jnp.float32
                a2 = jnp.asarray(arr).reshape(-1, arr.shape[-1])
                deq = ops.weight_transform(a2, jnp.asarray(scale),
                                           out_dtype=out_dt)
                flat[name] = deq.reshape(self._leaf_shape(abstract, name))
            elif self.apply_dtype is not None and \
                    np.issubdtype(arr.dtype, np.floating):
                flat[name] = ops.weight_transform(
                    jnp.asarray(arr).reshape(arr.shape[0], -1)
                    if arr.ndim >= 2 else jnp.asarray(arr)[None],
                    None, out_dtype=self.apply_dtype).reshape(arr.shape)
            else:
                put_names.append(name)
                put_arrs.append(arr)
        if qnames:
            bufs = jax.device_put(qvals + qscales)     # one batched transfer
            nq = len(qnames)
            for i, name in enumerate(qnames):
                flat[name] = QuantLeaf(bufs[i], bufs[nq + i])
        if put_arrs:
            flat.update(zip(put_names, jax.device_put(put_arrs)))
        tree = unflatten_unit(abstract, flat)
        return jax.block_until_ready(tree)

    def _apply_unit(self, unit: str, abstract: PyTree, leaves):
        """A_i: returns ``(compute_tree, mesh_tree_or_None)``.

        compute_tree lives on the default device and feeds the
        pipeline's E — byte-for-byte the single-device application, so
        the first request's logits are bit-identical regardless of the
        mesh (the per-shard transform is elementwise: dequant/cast of a
        slice equals the slice of the dequant/cast).  mesh_tree (mesh
        mode only) is the unit's steady-state sharded leaves: stitched
        from the shards' eagerly-committed — transformed, for
        dequant/cast leaves — device buffers where possible, raw
        per-device transfers otherwise."""
        data: Optional[ShardedUnitData] = None
        if isinstance(leaves, ShardedUnitData):
            data = leaves
            leaves = data.host_leaves()
        compute = self._apply_leaves(
            unit, abstract, leaves,
            prefetched=data.compute_bufs if data is not None else None)
        if self.mesh is None:
            return compute, None
        specs = data.plan.specs if data is not None else self._specs(unit)
        flatc = {
            leaf_path_name(path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(compute)[0]}
        dev = {}
        # leaves without committed buffers are placed here: raw
        # per-device transfers in one batch + a metadata stitch (a
        # device_put against a NamedSharding would route through the
        # resharding machinery — far slower on the apply path)
        pending = []                    # (name, sharding, imap)
        put_arrs, put_devs = [], []
        for name, (arr, scale) in leaves.items():
            transformed = scale is not None or (
                self.apply_dtype is not None and
                np.issubdtype(arr.dtype, np.floating))
            if data is not None and data.plan.commit[name]:
                dev[name] = data.global_array(name)    # metadata stitch
                continue
            sharding = specs[name]
            host = np.asarray(flatc[name]) if transformed else arr
            imap = sharding.devices_indices_map(tuple(host.shape))
            pending.append((name, sharding, imap))
            for d, idx in imap.items():
                put_arrs.append(host[idx])
                put_devs.append(d)
        if put_arrs:
            bufs = iter(jax.device_put(put_arrs, put_devs))
            for name, sharding, imap in pending:
                shape = tuple(self._leaf_shape(abstract, name))
                dev[name] = jax.make_array_from_single_device_arrays(
                    shape, sharding, [next(bufs) for _ in imap])
        # not block_until_ready: only the compute tree gates E — the
        # steady-state placement drains during E and is awaited by the
        # final assemble
        mesh_tree = unflatten_unit(abstract, dev)
        return compute, mesh_tree

    def _placement(self) -> Optional[Placement]:
        """Where a single-device load builds its steady-state params;
        None under a mesh, whose params are assembled on the mesh from
        the committed shards."""
        return Placement(self.model) if self.mesh is None else None

    def _assemble_sharded(self, units_dev: Dict[str, PyTree]) -> PyTree:
        if self._assemble_jit is None:
            out_specs = param_specs(self.model.abstract(), self.mesh,
                                    self.rules)
            self._assemble_jit = jax.jit(self.model.assemble,
                                         out_shardings=out_specs)
        return jax.block_until_ready(self._assemble_jit(units_dev))

    @staticmethod
    def _leaf_shape(abstract: PyTree, name: str):
        flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
        for path, leaf in flat:
            if leaf_path_name(path) == name:
                return leaf.shape
        raise KeyError(name)

    # ----------------------------------------------------------------- load
    def load(self, batch: Dict[str, jax.Array], *,
             key: Optional[jax.Array] = None,
             on_logits: Optional[Any] = None) -> LoadResult:
        """Serve one cold-start request end-to-end.

        on_logits: called with the request's logits the moment the
        final unit's E completes (inside the pipeline, before drain +
        assemble) — the generation path samples the first token here so
        a cold generation request's TTFT lands within the pipeline
        trace instead of after load + a separate prefill."""
        strat = self.strategy
        model = self.model
        units = model.unit_names()
        key = key if key is not None else jax.random.key(0)
        keys = list(jax.random.split(key, len(units)))

        trace = PipelineTrace()
        scheduler = PriorityAwareScheduler(enabled=strat.scheduler)
        state = PipelineState()
        sharded = self.mesh is not None and strat.decouple
        dec = WeightDecoupler(self.store, self.model_name, scheduler, trace,
                              io_workers=self.io_workers,
                              chunk_bytes=self.chunk_bytes, state=state,
                              cache=self.cache if strat.decouple else None,
                              source=self.source if strat.decouple
                              and self.cache is not None else None,
                              plan_fn=self._plan if sharded else None)
        with jax.profiler.TraceAnnotation("coldstart.load"):
            trace.start()
            try:
                if not strat.pipelined:
                    result = self._load_traditional(
                        batch, units, keys, trace, dec, on_logits)
                else:
                    result = self._load_pipelined(
                        batch, units, keys, trace, dec, scheduler, state,
                        on_logits)
            finally:
                # shutdown now guards shared-cache invariants (pin sweep +
                # unregister_load), so it must run on the failure path too
                dec.shutdown()
            trace.finish()
        self._record_load(trace)
        return result

    # 0..1 in even tenths — utilization is a ratio, not a latency, so
    # the log-spaced second buckets would collapse it into two bins
    UTIL_BUCKETS = tuple(i / 10 for i in range(1, 11))

    def _record_load(self, trace: PipelineTrace):
        """Per-load instruments: pipeline time, utilization, and the
        paper's per-stage waiting times (Q3) as live histograms."""
        m = self.metrics
        m.counter("coldstart/loads").inc()
        m.histogram("coldstart/load_s").observe(trace.total_time())
        m.histogram("coldstart/utilization",
                    buckets=self.UTIL_BUCKETS).observe(trace.utilization())
        wait = trace.wait_by_stage()
        m.histogram("pipeline/wait_A_s").observe(wait.get("A", 0.0))
        m.histogram("pipeline/wait_E_s").observe(wait.get("E", 0.0))

    # ------------------------------------------------- traditional (Fig. 1)
    def _load_traditional(self, batch, units, keys, trace, dec,
                          on_logits=None) -> LoadResult:
        constructed = {}
        for u, k in zip(units, keys):                    # all L
            with trace.record("L", u):
                constructed[u] = miniloader.construct_unit(
                    self.model, u, k, mini=False,
                    mesh=self.mesh, rules=self.rules)
        placement = self._placement()
        applied = {}                 # under a mesh: E's default-device trees
        sharded = {}
        for u in units:                                  # monolithic W+A
            with trace.record("R", u):                   # unit idles (DMA)
                leaves = dec.fetch_sync(u)               # blocking I/O
            with trace.record("A", u):
                tree, mesh_tree = self._apply_unit(
                    u, constructed[u].abstract, leaves)
            if placement is not None:
                placement.place(u, tree)
            else:
                applied[u], sharded[u] = tree, mesh_tree
            del tree
            trace.record_memory(u, constructed[u].mem_bytes,
                                constructed[u].t_construct_end,
                                time.monotonic())
        state: Dict[str, Any] = {"batch": batch}
        for u in units:                                  # all E
            with trace.record("E", u):
                uparams = placement.view(u) if placement is not None \
                    else applied.pop(u)
                state = self._apply_fn(u)(uparams, state)
                del uparams
                jax.block_until_ready(
                    state["logits" if u == units[-1] else "x"])
                if u == units[-1] and on_logits is not None:
                    on_logits(state["logits"])
        with jax.profiler.TraceAnnotation("coldstart.assemble"):
            params = self._assemble_sharded(sharded) \
                if placement is None else placement.params()
        return LoadResult(state["logits"], params, trace,
                          self.strategy.name)

    # ------------------------------------------------------- pipelined path
    def _load_pipelined(self, batch, units, keys, trace, dec,
                        scheduler, state: PipelineState,
                        on_logits=None) -> LoadResult:
        strat = self.strategy
        if strat.decouple:
            dec.prefetch(units)                 # issue I/O at request arrival

        placement = self._placement()
        ctx = PipelineContext(model=self.model, units=list(units),
                              keys=list(keys), batch=batch, strategy=strat,
                              trace=trace, decoupler=dec, scheduler=scheduler,
                              state=state, apply_leaves=self._apply_unit,
                              apply_fn=self._apply_fn, on_output=on_logits,
                              place=None if placement is None
                              else placement.place,
                              mesh=self.mesh, rules=self.rules)
        PipelineRuntime(standard_units(ctx), state).run()

        with jax.profiler.TraceAnnotation("coldstart.assemble"):
            params = self._assemble_sharded(state.peek(SHARDED)) \
                if placement is None else placement.params()
        return LoadResult(state.get(OUTPUT, "logits"), params, trace,
                          strat.name)
