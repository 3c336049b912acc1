"""Event-driven pipeline execution units (paper Fig. 2 decomposition).

The cold-start pipeline is three cooperating execution units — Layer
construction, Weight handling, Compute — that the seed implementation
expressed as inline thread closures synchronized by fixed-interval
``cv.wait(0.02)`` polling.  This module turns them into first-class,
composable objects:

  * :class:`PipelineState` — a shared blackboard: per-(stage, unit)
    completion slots guarded by **one** condition variable.  Producers
    :meth:`publish`, consumers :meth:`wait_for` / :meth:`wait_until`;
    every wait is woken by notification (or an explicit Algorithm-1
    deadline), never by a polling interval.
  * :class:`PipelineUnit` — base class for an execution unit; concrete
    units are :class:`LayerConstructionUnit`,
    :class:`DecoupledWeightUnit` (async retrieval, out-of-order
    application), :class:`FusedWeightUnit` (PISeL: retrieval fused,
    strictly ordered) and :class:`ComputeUnit`.
  * :class:`PipelineRuntime` — runs a unit set as threads and
    propagates the first failure.

New unit kinds (e.g. a host-to-device transfer unit between Weight and
Compute) subclass :class:`PipelineUnit`, consume/produce stages on the
shared state, and slot into the same runtime — no engine changes.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from repro import analysis
from repro.core import miniloader
from repro.core.pipeline import PipelineTrace
from repro.core.scheduler import PriorityAwareScheduler
from repro.core.strategies import Strategy

PyTree = Any

# Canonical stage names on the blackboard.  The letters match the
# PipelineTrace rows: L produces CONSTRUCTED, A produces APPLIED, E
# produces OUTPUT.  Under a mesh the weight units additionally publish
# SHARDED: the unit's steady-state leaves as mesh-committed
# NamedSharding arrays (stitched from the shards' eager device_puts),
# which the engine assembles into the sharded scan-stacked params.
CONSTRUCTED = "constructed"
APPLIED = "applied"
SHARDED = "sharded"
OUTPUT = "output"


class PipelineState:
    """Shared completion slots for one pipeline run, one condition
    variable for all signaling.

    The condition variable is exposed (``state.cv``) so collaborating
    components that complete work on other threads — the
    WeightDecoupler's I/O pool — can share it: their completions then
    wake any unit blocked here without a second lock or a poll loop.
    """

    def __init__(self, cv: Optional[threading.Condition] = None):
        self.cv = cv if cv is not None \
            else analysis.make_condition("PipelineState.cv")
        self._slots: Dict[str, Dict[str, Any]] = {}   # guarded-by: cv
        self.errors: List[BaseException] = []         # guarded-by: cv

    # ------------------------------------------------------------ producers
    def publish(self, stage: str, unit: str, value: Any = True):
        with self.cv:
            self._slots.setdefault(stage, {})[unit] = value
            self.cv.notify_all()

    def fail(self, exc: BaseException):
        with self.cv:
            if not any(e is exc for e in self.errors):
                self.errors.append(exc)
            self.cv.notify_all()

    # ------------------------------------------------------------ consumers
    def peek(self, stage: str) -> Dict[str, Any]:
        with self.cv:
            return dict(self._slots.get(stage, {}))

    def get(self, stage: str, unit: str) -> Any:
        with self.cv:
            return self._slots.get(stage, {}).get(unit)

    def wait_until(self, predicate: Callable[[], Any], *,
                   deadline_fn: Optional[Callable[[], Optional[float]]] = None,
                   on_deadline: Optional[Callable[[], None]] = None) -> Any:
        """Block until ``predicate()`` (evaluated under the lock) returns
        non-None; re-raises the first pipeline error.

        ``deadline_fn`` may supply a wake-up delay in seconds (None = no
        deadline).  When the deadline expires before a notification,
        ``on_deadline`` runs once and the deadline is re-asked — this is
        how Algorithm 1 fires exactly at a late stream's expected
        completion instead of on a polling grid.
        """
        with self.cv:
            while True:
                if self.errors:
                    raise self.errors[0]
                value = predicate()
                if value is not None:
                    return value
                wait_s = deadline_fn() if deadline_fn is not None else None
                if wait_s is not None and wait_s <= 0:
                    if on_deadline is not None:
                        on_deadline()
                    continue
                self.cv.wait(wait_s)

    def wait_for(self, stage: str, unit: str) -> Any:
        return self.wait_until(
            lambda: self._slots.get(stage, {}).get(unit))


@dataclasses.dataclass
class PipelineContext:
    """Everything a unit needs for one cold-start run."""
    model: Any
    units: List[str]                     # layer order
    keys: List[jax.Array]
    batch: Dict[str, jax.Array]
    strategy: Strategy
    trace: PipelineTrace
    decoupler: Any                       # WeightDecoupler
    scheduler: PriorityAwareScheduler
    state: PipelineState
    # (unit, abstract, retrieved) -> (compute_tree, mesh_tree_or_None):
    # compute_tree feeds E on the default device (bit-identical to the
    # single-device path); mesh_tree is the unit's steady-state sharded
    # leaves when a mesh is attached (None otherwise)
    apply_leaves: Callable[[str, PyTree, Any], Any]
    apply_fn: Callable[[str], Callable]
    # sharded cold start: resolved mesh + rules (None -> seed path)
    mesh: Any = None
    rules: Any = None
    # Called with the request's logits as soon as the final unit's E
    # completes them — while that E event is still open, before the
    # pipeline drains/assembles.  This is how a cold *generation*
    # request's first token is produced inside the pipeline (TTFT ~
    # E-completion, not load + separate prefill).
    on_output: Optional[Callable[[Any], None]] = None

    def index(self, unit: str) -> int:
        return self.units.index(unit)


class PipelineUnit:
    """One execution unit; runs on its own thread via PipelineRuntime."""

    name = "pipeline-unit"

    def __init__(self, ctx: PipelineContext):
        self.ctx = ctx

    def run(self):                       # pragma: no cover - interface
        raise NotImplementedError

    def thread(self) -> threading.Thread:
        def _runner():
            try:
                self.run()
            except BaseException as e:
                self.ctx.state.fail(e)
        return threading.Thread(target=_runner, name=self.name)


class LayerConstructionUnit(PipelineUnit):
    """L_i: build unit structures in order (MiniLoader or full init)."""

    name = "layer-unit"

    def run(self):
        ctx = self.ctx
        for u, k in zip(ctx.units, ctx.keys):
            if ctx.strategy.scheduler:
                # Algorithm 1 at L_i — for the layer the pipeline needs
                # NEXT (lowest un-applied), not the one being built:
                # prioritizing u_i itself would march criticality ahead
                # of the weight unit and park exactly the streams it is
                # waiting on (pathological with per-shard streams)
                applied = ctx.state.peek(APPLIED)
                needed = next((x for x in ctx.units if x not in applied),
                              u)
                ctx.scheduler.adjust_priority(needed)
            with ctx.trace.record("L", u):
                cu = miniloader.construct_unit(ctx.model, u, k,
                                               mini=ctx.strategy.mini,
                                               mesh=ctx.mesh,
                                               rules=ctx.rules)
            ctx.state.publish(CONSTRUCTED, u, cu)


class DecoupledWeightUnit(PipelineUnit):
    """A_i out of order: apply any unit whose structure is built and
    whose retrieval stream (issued at request arrival) has landed."""

    name = "weight-unit"

    def run(self):
        ctx = self.ctx
        dec = ctx.decoupler
        # bytes-ready signals must arrive on the state's CV, or waits
        # below would sleep through them (silent hang) — fail fast
        assert dec.cv is ctx.state.cv, \
            "WeightDecoupler must share the PipelineState CV (state=...)"
        pending = set(ctx.units)
        while pending:
            u = self._next_ready(pending)
            cu = ctx.state.get(CONSTRUCTED, u)
            with ctx.trace.record("A", u):
                params, mesh_tree = ctx.apply_leaves(u, cu.abstract,
                                                     dec.ready[u])
            dec.checkin(u)      # application done: drop the cache pins
            ctx.trace.record_memory(u, cu.mem_bytes, cu.t_construct_end,
                                    time.monotonic())
            if mesh_tree is not None:
                ctx.state.publish(SHARDED, u, mesh_tree)
            ctx.state.publish(APPLIED, u, params)
            pending.discard(u)

    def _next_ready(self, pending) -> str:
        """Lowest-index pending unit with structure + bytes ready.

        While blocked, wake exactly at the *critical* unit's expected
        completion (the one the compute unit needs next) and run
        Algorithm 1 so a late stream gets the full I/O bandwidth.
        """
        ctx = self.ctx
        dec = ctx.decoupler
        critical = min(pending, key=ctx.index)

        def _avail() -> Optional[str]:
            built = ctx.state._slots.get(CONSTRUCTED, {})
            got = [u for u in pending if u in built and u in dec.ready]
            return min(got, key=ctx.index) if got else None

        deadline = (ctx.scheduler.time_until_expected
                    if ctx.strategy.scheduler else None)
        return ctx.state.wait_until(
            _avail,
            deadline_fn=(lambda: deadline(critical)) if deadline else None,
            on_deadline=lambda: ctx.scheduler.adjust_priority(critical))


class FusedWeightUnit(PipelineUnit):
    """PISeL W_i: retrieval fused into the unit, strictly ordered after
    L_i — the unit idles on I/O (that idleness is the paper's point)."""

    name = "weight-unit"

    def run(self):
        ctx = self.ctx
        for u in ctx.units:
            cu = ctx.state.wait_for(CONSTRUCTED, u)
            with ctx.trace.record("R", u):
                leaves = ctx.decoupler.fetch_sync(u)
            with ctx.trace.record("A", u):
                params, mesh_tree = ctx.apply_leaves(u, cu.abstract, leaves)
            ctx.trace.record_memory(u, cu.mem_bytes, cu.t_construct_end,
                                    time.monotonic())
            if mesh_tree is not None:
                ctx.state.publish(SHARDED, u, mesh_tree)
            ctx.state.publish(APPLIED, u, params)


class ComputeUnit(PipelineUnit):
    """E_i: run layer i as soon as its weights are applied — the
    triggering request is answered while the model is still loading."""

    name = "compute-unit"

    def run(self):
        ctx = self.ctx
        st: Dict[str, Any] = {"batch": ctx.batch}
        last = ctx.units[-1]
        for u in ctx.units:
            with jax.profiler.TraceAnnotation("coldstart.E.wait", unit=u):
                params = ctx.state.wait_for(APPLIED, u)
            with ctx.trace.record("E", u):
                st = ctx.apply_fn(u)(params, st)
                jax.block_until_ready(st["logits" if u == last else "x"])
                if u == last and ctx.on_output is not None:
                    # first token sampled inside the final E event
                    ctx.on_output(st["logits"])
        ctx.state.publish(OUTPUT, "logits", st["logits"])


class PipelineRuntime:
    """Run a set of units to completion; surface the first error."""

    def __init__(self, units: Sequence[PipelineUnit], state: PipelineState):
        self.units = list(units)
        self.state = state

    def run(self):
        threads = [u.thread() for u in self.units]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if self.state.errors:
            raise self.state.errors[0]


def standard_units(ctx: PipelineContext) -> List[PipelineUnit]:
    """The paper's three-unit pipeline for a strategy: the same runtime
    drives both the fused (PISeL) and decoupled weight paths."""
    weight_cls = (DecoupledWeightUnit if ctx.strategy.decouple
                  else FusedWeightUnit)
    return [LayerConstructionUnit(ctx), weight_cls(ctx), ComputeUnit(ctx)]
