"""WeightDecoupler — asynchronous file retrieval + out-of-order
application support (paper Sec. III-C / III-D).

Weight loading has two phases with a ~4:1 cost ratio (paper Fig. 5c):

  * **file retrieval** (I/O-bound): chunked extent read + deserialize +
    crc — runs on an I/O thread pool, *issued at request arrival* so it
    overlaps layer construction.  Each stream carries a suspension gate
    owned by the Priority-Aware Scheduler.
  * **weight application** (compute-bound): dequant/cast via the
    ``weight_transform`` kernel + device placement — performed by the
    Weight execution unit, *out of order*: any unit whose bytes and
    structure are both ready can be applied.

With a ``Mesh`` attached, retrieval is **shard-granular**: every unit
fans out into one stream per mesh device (a :class:`~repro.core.shards.
UnitShardPlan`), each stream reading only the byte ranges of the leaf
slices its device owns, on its own simulated-device channel.  Streams
complete out of order *across shards, not just units* — a landed shard
is immediately committed to its target devices (``jax.device_put``
inside :meth:`ShardedUnitData.add_shard`) without waiting for
siblings, and ``ready[unit]`` publishes when the unit's **last** shard
lands.  Quantized/castable leaves participate too: their shard streams
carry value slices plus per-column scale slices, and the placement
lane runs the ``weight_transform`` kernel on each landed slice before
its commit — the weight-application *compute* phase is itself
pipelined per shard (Cicada's decoupling, pushed one level down).
Without a mesh the seed's unit-granular path is unchanged.

In the PISeL baseline the two phases are fused and strictly ordered;
``fetch_sync`` provides that path.

With a node-local :class:`~repro.store.cache.WeightCache` attached,
every stream consults the cache before issuing I/O: a hit publishes
its bytes immediately (a ~zero-cost "R" trace event, marked
``cached``), a miss single-flights the store read node-wide — cache
keys are ``(model, unit, shard)``, so concurrent scale-out onto the
same mesh stays zero-read per shard.  Cached entries stay pinned from
retrieval until weight application (released via :meth:`checkin`), so
eviction pressure can never reclaim bytes an in-flight — possibly
Algorithm-1-critical — load is about to apply.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import numpy as np

from repro import analysis
from repro.core.pipeline import PipelineTrace
from repro.core.scheduler import PriorityAwareScheduler
from repro.core.shards import ShardedUnitData, UnitShardPlan
from repro.core.units import PipelineState
from repro.store.cache import LOAD, WeightCache
from repro.store.store import WeightStore

PyTree = Any
Leaves = Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]


class ShardSource:
    """Where a stream's bytes come from when the local cache misses.

    The default source is the origin store: :meth:`fetch` just invokes
    the ``read_origin`` thunk the decoupler hands it.  A cluster tier
    (``repro.cluster.peer.ClusterShardSource``) overrides it to consult
    a cluster-wide placement table first and serve the payload from a
    peer node's cache over the fast intra-cluster link — the origin
    thunk then runs only when this node is elected the *cluster-wide*
    single-flight leader for the key.

    Contract with the decoupler (mirrors the WeightCache protocol):
    ``fetch`` returns ``(payload, src)`` with ``src`` in {"origin",
    "peer"}; after the payload is published to the local cache the
    decoupler calls :meth:`publish`, and on any failure between fetch
    and publish it calls :meth:`abort` (both no-ops here)."""

    def fetch(self, model: str, unit: str, skey: Hashable, nbytes: int,
              read_origin: Callable[[], Any], *,
              gate=None, on_chunk: Optional[Callable[[int], None]] = None
              ) -> Tuple[Any, str]:
        return read_origin(), "origin"

    def publish(self, model: str, unit: str, skey: Hashable):
        pass

    def abort(self, model: str, unit: str, skey: Hashable):
        pass


class WeightDecoupler:
    def __init__(self, store: WeightStore, model_name: str,
                 scheduler: PriorityAwareScheduler, trace: PipelineTrace,
                 *, io_workers: int = 4, chunk_bytes: int = 1 << 20,
                 state: Optional[PipelineState] = None,
                 cache: Optional[WeightCache] = None,
                 plan_fn: Optional[Callable[[str], UnitShardPlan]] = None,
                 source: Optional[ShardSource] = None):
        """``state``: a PipelineState whose condition variable this
        decoupler shares — stream completions then directly wake
        pipeline units blocked on that state (single-CV signaling, no
        cross-lock polling).  Standalone use gets a private CV.

        ``cache``: optional node-local WeightCache consulted before any
        I/O is issued (shared across engines/instances for scale-out
        reuse and single-flight reads).

        ``plan_fn``: unit -> UnitShardPlan — enables shard-granular
        retrieval (the engine supplies plans resolved from its mesh +
        sharding rules).  None keeps the seed's unit-granular streams.

        ``source``: where a local-cache miss reads its bytes (see
        :class:`ShardSource`) — a cluster peer tier substitutes the
        fast intra-cluster link for the origin store here.  Requires a
        cache: the source's publish step is what makes this node's
        resident copy visible to peers.
        """
        if source is not None and cache is None:
            raise ValueError("a ShardSource requires a WeightCache "
                             "(peers are served from this node's cache)")
        self.store = store
        self.model_name = model_name
        self.scheduler = scheduler
        self.trace = trace
        self.chunk_bytes = chunk_bytes
        self.cache = cache
        self.source = source
        self.plan_fn = plan_fn
        self._plans: Dict[str, UnitShardPlan] = {}
        self._mesh_tag: Optional[str] = None
        self.io_workers = io_workers
        # Created at prefetch, sized to the stream count: a suspended
        # stream parks INSIDE its worker (gate.wait mid-read), so a
        # pool smaller than the stream fan-out can wedge — every worker
        # held by a suspended stream while the critical unit's streams
        # sit queued, creeping forward only on deadline wakes.
        self._pool: Optional[ThreadPoolExecutor] = None
        self._committer: Optional[ThreadPoolExecutor] = None
        self._admit: Dict[str, threading.Event] = {}
        self.state = state
        self.cv = state.cv if state is not None \
            else analysis.make_condition("WeightDecoupler.cv")
        self._unadmitted: List[str] = []              # guarded-by: cv
        self._reads_left: Dict[str, int] = {}         # guarded-by: cv
        # unit -> Leaves (unit-granular) | ShardedUnitData (complete)
        self.ready: Dict[str, Any] = {}               # guarded-by: cv
        self.errors: List[BaseException] = []         # guarded-by: cv
        # (unit, shard-key) cache refs
        self._pinned: set = set()                     # guarded-by: cv
        self._load_registered = False
        self._closed = False                          # guarded-by: cv

    # ------------------------------------------------------ async retrieval
    def prefetch(self, units: List[str]):
        """Issue every retrieval stream now (at request arrival) — this is
        what lets retrieval overlap layer construction.  With a shard
        plan, that is ``n_units x n_shards`` independent streams."""
        if self.cache is not None and not self._load_registered:
            self.cache.register_load(self.model_name)
            self._load_registered = True
        if self.plan_fn is None:
            self._pool = ThreadPoolExecutor(max_workers=self.io_workers,
                                            thread_name_prefix="cicada-io")
            for u in units:
                nbytes = self.store.unit_nbytes(self.model_name, u)
                st = self.scheduler.register(u, nbytes)
                self._pool.submit(self._fetch, u, st)
            return
        streams = []
        # Unit admission window: only ``io_workers`` units' shard
        # streams read concurrently, admitted in pipeline order and
        # advanced as units finish.  With every stream admitted at
        # once they would fair-share the channels and ALL units would
        # land near the end of the load — no early unit for the
        # pipeline to construct/apply/execute against (the seed's
        # bounded I/O pool enforced this ordering implicitly).
        self._admit = {u: threading.Event() for u in units}
        # pre-thread initialization: the stream workers that share cv
        # are submitted only at the end of this method
        self._unadmitted = list(units)      # analysis: ignore[R1]
        self._reads_left = {}               # analysis: ignore[R1]
        for u in units:
            plan = self.plan_fn(u)
            self._plans[u] = plan
            data = ShardedUnitData(plan, trace=self.trace)
            if self._mesh_tag is None:
                self._mesh_tag = plan.tag
            self._reads_left[u] = plan.n_shards     # analysis: ignore[R1]
            for s in range(plan.n_shards):
                st = self.scheduler.register(u, plan.shard_nbytes(s),
                                             shard=s)
                streams.append((u, s, st, data))
        for _ in range(min(self.io_workers, len(units))):
            self._admit[self._unadmitted.pop(0)].set()  # analysis: ignore[R1]
        self._pool = ThreadPoolExecutor(
            max_workers=max(self.io_workers, len(streams)),
            thread_name_prefix="cicada-io")
        # dedicated placement lanes — the modeled per-device DMA
        # queues: host merges + device commits run here instead of on
        # the read threads (where they'd contend with every in-flight
        # stream), and still start the moment each shard lands
        lanes = min(4, max(p.n_shards for p in self._plans.values()))
        self._committer = ThreadPoolExecutor(
            max_workers=lanes, thread_name_prefix="cicada-commit")
        for u, s, st, data in streams:
            self._pool.submit(self._fetch_shard, u, s, st, data)

    # -------------------------------------------------- unit-granular path
    @staticmethod
    def _src_meta(src: str) -> Dict[str, Any]:
        """Trace annotation of a stream's byte source: origin reads are
        unmarked, cache hits carry ``cached``, peer-exchange transfers
        carry ``peer``."""
        return {"cache": {"cached": True}, "peer": {"peer": True}}.get(
            src, {})

    def _progress_cb(self, unit: str, total: int, shard: Hashable = 0):
        """Per-chunk progress callback for source-driven transfers
        (peer link): accumulates into the scheduler's stream state the
        way _read_store / _read_shard do for origin reads."""
        done = [0]
        t = max(1, int(total))

        def cb(n):
            done[0] += n
            self.scheduler.on_progress(unit, done[0], t, shard=shard)
        return cb

    def _fetch(self, unit: str, st):
        try:
            self.scheduler.on_issue(unit)
            with self.cv:           # waiters recompute Algorithm 1 deadlines
                self.cv.notify_all()
            with self.trace.record("R", unit) as meta:
                leaves, src = self._retrieve(unit, st)
                meta.update(self._src_meta(src))
            self.scheduler.on_complete(unit, observed=(src == "origin"))
            with self.cv:
                self.ready[unit] = leaves
                self.cv.notify_all()
        except BaseException as e:              # surfaced by the engine
            self.scheduler.on_error(unit)       # un-park suspended streams
            with self.cv:
                self.errors.append(e)
                if self.state is not None:
                    self.state.errors.append(e)
                self.cv.notify_all()

    def _retrieve(self, unit: str, st) -> Tuple[Leaves, str]:
        """One stream's bytes: cache hit / single-flight wait / leader
        read through the source (origin store, or a cluster peer's
        cache over the fast link).  Returns ``(leaves, src)`` with src
        in {"cache", "origin", "peer"}."""
        if self.cache is None:
            return self._read_store(unit, st), "origin"
        # A hit OR a wait on another load's read is "external" to this
        # pipeline's I/O: Algorithm 1 must not prioritize it (see
        # PriorityAwareScheduler.mark_external).  We cannot know which
        # before begin() may block, so flag optimistically and unflag
        # only if this stream ends up doing a genuine origin read (a
        # peer transfer is external too: suspending local device
        # streams cannot speed up another node's cache).
        self.scheduler.mark_external(unit)
        status, leaves = self.cache.begin(self.model_name, unit)
        if status == LOAD:
            def read_origin():
                self.scheduler.mark_external(unit, False)
                return self._read_store(unit, st)
            src = "origin"
            try:
                if self.source is None:
                    leaves = read_origin()
                else:
                    leaves, src = self.source.fetch(
                        self.model_name, unit, 0, st.nbytes, read_origin,
                        gate=st.gate,
                        on_chunk=self._progress_cb(unit, st.nbytes))
                self.cache.complete(self.model_name, unit, leaves,
                                    st.nbytes)
            except BaseException:
                self.cache.abort(self.model_name, unit)
                if self.source is not None:
                    self.source.abort(self.model_name, unit, 0)
                raise
            if self.source is not None:
                self.source.publish(self.model_name, unit, 0)
            self._pin(unit, 0)
            return leaves, src
        self._pin(unit, 0)
        return leaves, "cache"

    def _read_store(self, unit: str, st) -> Leaves:
        raw = self.store.read_unit(
            self.model_name, unit, chunk_bytes=self.chunk_bytes,
            gate=st.gate,
            on_progress=lambda d, t: self.scheduler.on_progress(
                unit, d, t))
        return self.store.deserialize(self.model_name, unit, raw)

    # ------------------------------------------------- shard-granular path
    def _shard_key(self, shard: int) -> Hashable:
        # cache identity: the same unit planned for a different mesh
        # shape OR different sharding rules holds different byte
        # ranges — never serve one as the other (the tag fingerprints
        # both; see shards.plan_tag)
        return (self._mesh_tag, shard)

    def _fetch_shard(self, unit: str, shard: int, st,
                     data: ShardedUnitData):
        try:
            self._admit[unit].wait()        # unit-ordered channel window
            with self.cv:
                if self._closed:            # released by shutdown
                    return
            self.scheduler.on_issue(unit, shard=shard)
            with self.cv:
                self.cv.notify_all()
            with self.trace.record("R", unit, {"shard": shard}) as meta:
                payload, src = self._retrieve_shard(unit, shard, st, data)
                meta.update(self._src_meta(src))
            self.scheduler.on_complete(unit, observed=(src == "origin"),
                                       shard=shard)
            with self.cv:                   # unit fully read: admit next
                self._reads_left[unit] -= 1
                if self._reads_left[unit] == 0 and self._unadmitted:
                    self._admit[self._unadmitted.pop(0)].set()
            # placement runs on the committer the moment the shard
            # lands — out-of-order across shards, no sibling barrier
            self._committer.submit(self._commit_shard, unit, shard,
                                   data, payload,
                                   self.cache is None)
        except BaseException as e:
            self.scheduler.on_error(unit, shard=shard)
            with self.cv:
                self.errors.append(e)
                if self.state is not None:
                    self.state.errors.append(e)
                self.cv.notify_all()

    def _commit_shard(self, unit: str, shard: int, data: ShardedUnitData,
                      payload, merged: bool):
        try:
            # host merge (cache path only) + per-shard weight_transform
            # of dequant/cast pieces + eager mesh commit; exactly one
            # lane — the unit-completing one, AFTER the compute
            # prefetch is in place — gets last=True and publishes
            last = data.add_shard(shard, payload, merged=merged)
            with self.cv:
                if last:
                    self.ready[unit] = data
                self.cv.notify_all()
        except BaseException as e:
            with self.cv:
                self.errors.append(e)
                if self.state is not None:
                    self.state.errors.append(e)
                self.cv.notify_all()

    def _retrieve_shard(self, unit: str, shard: int, st,
                        data: Optional[ShardedUnitData] = None):
        skey = self._shard_key(shard)
        if self.cache is None:
            # no cache: gather straight into the unit's full host
            # leaves (the cache path materializes standalone slices —
            # its payloads outlive this load)
            return self._read_shard(unit, shard, st, data), "origin"
        self.scheduler.mark_external(unit, shard=shard)
        status, payload = self.cache.begin(self.model_name, unit, skey)
        if status == LOAD:
            def read_origin():
                self.scheduler.mark_external(unit, False, shard=shard)
                return self._read_shard(unit, shard, st)
            src = "origin"
            try:
                if self.source is None:
                    payload = read_origin()
                else:
                    payload, src = self.source.fetch(
                        self.model_name, unit, skey, st.nbytes,
                        read_origin, gate=st.gate,
                        on_chunk=self._progress_cb(unit, st.nbytes,
                                                   shard))
                self.cache.complete(self.model_name, unit, payload,
                                    st.nbytes, skey)
            except BaseException:
                self.cache.abort(self.model_name, unit, skey)
                if self.source is not None:
                    self.source.abort(self.model_name, unit, skey)
                raise
            if self.source is not None:
                self.source.publish(self.model_name, unit, skey)
            self._pin(unit, skey)
            return payload, src
        self._pin(unit, skey)
        return payload, "cache"

    def _read_shard(self, unit: str, shard: int, st,
                    data: Optional[ShardedUnitData] = None):
        """One shard stream: byte-range reads of every leaf slice this
        shard owns, over the shard's own simulated-device channel.

        With ``data`` the gather lands directly in the unit's full host
        leaves (zero staging copies); without it (cache path) each
        slice is materialized standalone."""
        plan = self._plans[unit]
        total = max(1, plan.shard_nbytes(shard))
        done = [0]

        def on_chunk(n):
            done[0] += n
            self.scheduler.on_progress(unit, done[0], total, shard=shard)

        payload = []
        fh = self.store.open_unit(self.model_name, unit)
        try:
            for piece in plan.pieces[shard]:
                out = None
                if data is not None and piece.index is not None:
                    out = data.host_dest(piece.leaf, piece.index)
                arr, scale = self.store.read_leaf_slice(
                    self.model_name, unit, piece.leaf, piece.index,
                    fh=fh, chunk_bytes=self.chunk_bytes, gate=st.gate,
                    on_chunk=on_chunk, channel=shard, out=out)
                payload.append((piece.leaf, arr, scale, piece.index))
        finally:
            fh.close()
        return payload

    # ------------------------------------------------------ cache bookkeeping
    def _pin(self, unit: str, skey: Hashable):
        with self.cv:
            if not self._closed:
                self._pinned.add((unit, skey))
                return
        # shutdown already swept pins: release straight away
        self.cache.release(self.model_name, unit, skey)

    def checkin(self, unit: str):
        """Weight application of ``unit`` is done: drop the cache pins
        of all its shards (no-op without a cache)."""
        if self.cache is None:
            return
        with self.cv:
            mine = [(u, k) for (u, k) in self._pinned if u == unit]
            self._pinned.difference_update(mine)
        for u, k in mine:
            self.cache.release(self.model_name, u, k)

    # ------------------------------------------------------ sync (PISeL)
    def fetch_sync(self, unit: str) -> Leaves:
        """Blocking retrieval + deserialize — the fused W_i of PISeL."""
        raw = self.store.read_unit(self.model_name, unit,
                                   chunk_bytes=self.chunk_bytes)
        return self.store.deserialize(self.model_name, unit, raw)

    # -------------------------------------------------------------- waiting
    # (Waiting for ready bytes lives in DecoupledWeightUnit._next_ready:
    # it needs construction state too, and shares this decoupler's CV.)

    def shutdown(self):
        with self.cv:
            # _closed flips under cv so a shard worker passing its
            # admission gate observes it or the pin sweep sees its pin
            # — never neither (the old unlocked write raced _pin)
            self._closed = True
            pinned, self._pinned = self._pinned, set()
            self.cv.notify_all()
        for ev in self._admit.values():     # release admission waiters
            ev.set()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        if self._committer is not None:
            self._committer.shutdown(wait=False)
        if self.cache is not None:
            for u, k in pinned:              # pins left by an aborted load
                self.cache.release(self.model_name, u, k)
            if self._load_registered:
                self._load_registered = False
                self.cache.unregister_load(self.model_name)
