"""One run of one cell: deploy, build the platform, warm up the cell's
shapes, drive its traffic through ``Router.submit`` for the window, then
free the program's state and check what it served.

Everything on the host that the benchmark does around the program is
wrapped in ``jax.profiler.TraceAnnotation`` spans named ``bench.*``, so
a traced run can say what the host was doing in the device's idle gaps.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import check, spec as spec_mod, traffic, weights
from bench.lib.compile_stats import CompileStats

WAIT_PAST_CLOSE_S = traffic.WAIT_PAST_CLOSE_S
WARM_UP_IN_FLIGHT = 6


@dataclasses.dataclass
class Record:
    """One request as the benchmark saw it.  Times are
    ``time.monotonic()`` seconds."""
    index: int
    n_prompt: int
    n_new: int
    due: Optional[float] = None       # open loop: scheduled arrival
    t_submit: Optional[float] = None
    t_first: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    prompt: Optional[np.ndarray] = None
    cold: bool = False
    queue_s: float = 0.0
    utilization: Optional[float] = None
    construct_s: Optional[float] = None
    ok: bool = False
    error: Optional[str] = None

    @property
    def arrival(self) -> float:
        """When the request was due: its schedule in an open loop, its
        submission in a closed one."""
        return self.due if self.due is not None else self.t_submit


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Any
    seed: int
    seconds: float
    w0: float
    w1: float
    records: List[Record]
    counters: Dict[str, float]
    trace: Any = None                 # bench.lib.trace.View, traced runs
    trace_window: Optional[tuple] = None   # (t0, t1) monotonic
    peaks: Any = None
    setup_s: Optional[float] = None

    @property
    def cfg(self) -> Dict[str, Any]:
        return self.cell.config

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.w0 <= t < self.w1

    def decode_steps(self, t0: float, t1: float) -> List[List[int]]:
        """The decode steps whose tokens came back in [t0, t1): for each,
        the context of every live row (a token emitted i-th after the
        first attends to n_prompt + i cached positions)."""
        steps: Dict[float, List[int]] = {}
        for r in self.records:
            if not r.ok:
                continue
            for i, t in enumerate(r.times[1:], start=1):
                if t0 <= t < t1:
                    steps.setdefault(round(t, 6), []).append(r.n_prompt + i)
        return [steps[k] for k in sorted(steps)]


def _arch(cfg: Dict[str, Any]):
    """The program's config object for a configuration file."""
    from repro.models.api import ArchConfig, Family
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    s = cfg["serving"]
    return ArchConfig(
        name=cfg["name"], family=Family.DENSE,
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=dtype[s["param_dtype"]],
        compute_dtype=dtype[s["compute_dtype"]])


def _span(name: str):
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class Cell:
    """The program under test for one cell, built and warmed up."""

    def __init__(self, cell, seed: int, seconds: float, *,
                 stats: CompileStats, log: Callable[[str], None]):
        from repro.models import transformer
        from repro.serving.engine import ServerlessPlatform
        from repro.store.store import WeightStore

        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.cfg, self.mix, self.kind = cell.config, cell.traffic, cell.kind
        self.stats, self.log = stats, log
        self.model = transformer.build(_arch(self.cfg))
        self.name = self.cfg["name"]
        self.plan = self.kind.plan(self.mix, seed, seconds)
        self._ids = itertools.count()
        s = self.cfg["serving"]

        with _span("deploy"):
            t0 = time.monotonic()
            self.store_dir = tempfile.mkdtemp(prefix="bench-store-")
            self.store = WeightStore(self.store_dir)
            units = weights.make(self.cfg, seed)
            self._check_layout(units)
            self.store.deploy(self.name, units)
            del units
            log(f"deploy: {self.store.model_nbytes(self.name) / 1e9:.3f} GB "
                f"in {time.monotonic() - t0:.1f}s")

        example = {"tokens": jnp.zeros((1, self.shapes()[0][0]), jnp.int32)}
        self.platform = ServerlessPlatform(
            self.store, {self.name: lambda: (self.model, example)},
            strategy=s["strategy"], keep_alive_s=1e6, max_instances=1,
            gen_slots=s["gen_slots"], gen_cache_len=s["gen_cache_len"],
            kv_page_tokens=s["kv_page_tokens"],
            kv_budget_bytes=s["kv_budget_bytes"])
        clients = int(self.mix.get("clients", 0))
        self.router = self.platform.router(
            workers=max(clients, s["gen_slots"]) + 8)

    span = staticmethod(_span)

    # ----------------------------------------------------------- set-up
    def shapes(self) -> List[tuple]:
        """Every (n_prompt, n_new) pair of this run's plan."""
        return sorted({(p.n_prompt, p.n_new) for p in self.plan})

    def _check_layout(self, units):
        """The benchmark's weights have the program's unit shapes."""
        for u, tree in units.items():
            want = jax.tree.map(lambda a: a.shape, self.model.abstract_unit(u))
            got = jax.tree.map(lambda a: a.shape, tree)
            if want != got:
                raise RuntimeError(f"unit {u}: benchmark layout {got} is "
                                   f"not the program's {want}")

    def request(self, n_prompt: int, n_new: int, index: int,
                due: Optional[float] = None, warm_up: bool = False
                ) -> Record:
        vocab = self.cfg["vocab_size"]
        if hasattr(self.kind, "prompt_tokens"):
            prompt = self.kind.prompt_tokens(self.mix, self.seed, index,
                                             n_prompt, vocab, warm_up)
        else:
            prompt = traffic.prompt_tokens(self.seed, index, n_prompt,
                                           vocab, warm_up)
        return Record(index=index, n_prompt=n_prompt, n_new=n_new, due=due,
                      prompt=prompt)

    def submit(self, rec: Record):
        """Send one request; returns its future."""
        from repro.serving.api import GenerateSpec, Request
        spec = GenerateSpec(prompt=rec.prompt, n_new=rec.n_new,
                            temperature=float(self.mix.get("temperature", 0)))
        with _span("router.submit"):
            rec.t_submit = time.monotonic()
            return self.router.submit(Request(req_id=next(self._ids),
                                              model=self.name, gen=spec))

    def finish(self, rec: Record, fut, timeout: float):
        """Wait for a request and fill its record from the Response."""
        try:
            r = fut.result(timeout=timeout)
        except Exception as e:             # refused, failed or never came
            rec.error = f"{type(e).__name__}: {e}"
            return
        rec.t_first = r.t_arrival + r.ttft_s
        rec.times = list(rec.t_first + np.concatenate(
            [[0.0], np.cumsum(r.tpot_s)]))
        rec.tokens = [int(t) for t in r.tokens]
        rec.cold, rec.queue_s = bool(r.cold), float(r.queue_s)
        if r.cold:
            rec.utilization = float(r.utilization)
        rec.ok = True

    def last_load_construct_s(self) -> float:
        inst = self.platform.pools[self.name]._instances[0]
        return float(inst.last_load.trace.work_by_stage().get("L", 0.0))

    def warm_up(self):
        """Make the model live and run every shape of this run's plan
        once, so nothing compiles inside the window.  A cold cell's
        set-up makes one cold start, which compiles the per-unit
        programs, the prefill and the decode step.  At most
        ``WARM_UP_IN_FLIGHT`` requests run at once, longest first: every
        prefill holds its whole prompt's logits and cache until it
        joins the batch."""
        shapes = self.shapes()
        with _span("warm_up"):
            first = self.request(*shapes[0], index=0, warm_up=True)
            self.finish(first, self.submit(first), timeout=1200)
            if not first.ok:
                raise RuntimeError(f"warm-up request failed: {first.error}")
            recs = [self.request(p, o, index=i, warm_up=True)
                    for i, (p, o) in enumerate(shapes[1:], start=1)]
            recs.sort(key=lambda r: -r.n_new)
            for k in range(0, len(recs), WARM_UP_IN_FLIGHT):
                wave = recs[k:k + WARM_UP_IN_FLIGHT]
                futs = [self.submit(r) for r in wave]
                for r, f in zip(wave, futs):
                    self.finish(r, f, timeout=1200)
                    if not r.ok:
                        raise RuntimeError(
                            f"warm-up request failed: {r.error}")
        self.log(f"warm-up: {len(shapes)} shapes {shapes}")

    # ----------------------------------------------------------- window
    def counters(self) -> Dict[str, float]:
        m = self.platform.metrics
        out = {"decode_steps": m.counter("decode/steps").value,
               "cold_starts": m.counter(f"pool/{self.name}/cold_starts")
               .value}
        out.update({f"compile_{k}": v for k, v in self.stats.snapshot()
                    .items()})
        # the host's share: this process's CPU time and garbage collections
        out["host_cpu_s"] = time.process_time()
        out["gc_collections"] = sum(g["collections"] for g in gc.get_stats())
        return out

    def drive(self, tracer=None) -> Run:
        lead = float(self.mix.get("lead_in_s", 0.0))
        t_start = time.monotonic()
        w0 = t_start + lead
        w1 = w0 + self.seconds
        marks: Dict[str, Dict[str, float]] = {}

        def at_window(key, when):
            def mark():
                time.sleep(max(0.0, when - time.monotonic()))
                marks[key] = self.counters()
            th = threading.Thread(target=mark, name=f"bench-mark-{key}")
            th.start()
            return th

        markers = [at_window("w0", w0), at_window("w1", w1)]
        if tracer is not None:
            tracer.start(w0)
        with _span("window"):
            records = self.kind.drive(self, self.plan, t_start, w1)
        for th in markers:
            th.join()
        if tracer is not None:
            tracer.join()
        c0, c1 = marks["w0"], marks["w1"]
        return Run(cell=self.cell, seed=self.seed, seconds=self.seconds,
                   w0=w0, w1=w1, records=records,
                   counters={k: c1[k] - c0[k] for k in c0},
                   trace_window=None if tracer is None else tracer.window)

    # ---------------------------------------------------------- teardown
    def close(self):
        """Stop the router and drop every reference to the program's
        state, so that the reference can run after it."""
        self.router.shutdown(wait=True)
        self.router = None
        self.platform = None
        gc.collect()
        shutil.rmtree(self.store_dir, ignore_errors=True)


def correctness(cell_spec, run: Run, *, control: bool = False
                ) -> Dict[str, Any]:
    """Replay a sample of the served requests through the reference.
    Returns the widest gap, and with ``control`` the control's."""
    cfg = cell_spec.config
    chk = cfg["check"]
    picked = check.sample(run.records, run.seed, chk["min_tokens"],
                          chk["max_requests"])
    if not picked:
        return {"max_gap": None, "tokens": 0, "requests": 0}
    ref = spec_mod.load_module("reference", cfg["reference"])
    w = weights.make(cfg, run.seed)
    length = cfg["serving"]["gen_cache_len"]
    served, ctrl = [], []
    for r in picked:
        g = check.replay(ref, cfg, w, r.prompt, r.tokens, length,
                         control=control)
        served.append(g["served"])
        if control:
            ctrl.append(g["control"])
    del w
    out = {"max_gap": float(np.max(np.concatenate(served))),
           "tokens": int(sum(len(s) for s in served)),
           "requests": len(picked)}
    if control:
        out["control_max_gap"] = float(np.max(np.concatenate(ctrl)))
    return out


def log_to_stderr(msg: str):
    print(msg, file=sys.stderr, flush=True)
