"""The program's profiler host spans, on the CPU.

One cold load (Cicada strategy) and two overlapping paged generation
requests run under ``jax.profiler``; the trace is read back with the
benchmark's loader (``bench/lib/trace.py``), which is what the
benchmark's per-layer readers see.  Which host thread ran a span comes
from the profiler's own lines.
"""
import glob
import os
import sys
import threading
import types
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ColdStartEngine
from repro.models import transformer
from repro.models.api import get_config
from repro.serving import DecodeScheduler, GenerateSpec
from repro.store.store import WeightStore, deploy_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench.lib import harness, spec, trace as trace_mod  # noqa: E402

PT = 16
PROMPTS = (19, 8)
N_NEW = 6


def _host_lines(path):
    """Each host thread's program spans: (name, start_ns, end_ns,
    stats) rows, one list per profiler line."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.split(".")[0] in ("coldstart", "decode")]
            if rows:
                out.append(rows)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = get_config("smollm-360m", smoke=True)
    m = transformer.build(cfg)
    store = WeightStore(str(tmp_path_factory.mktemp("store")))
    deploy_model(store, m, "m", jax.random.key(3))
    r = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(r.integers(0, cfg.vocab_size, (1, 16)),
                                   jnp.int32)}
    eng = ColdStartEngine(m, "m", store, strategy="cicada",
                          chunk_bytes=1 << 15)
    eng.warmup(batch)
    params = m.init(jax.random.key(0))
    sched = DecodeScheduler(m, params, n_slots=2, cache_len=64,
                            kv_page_tokens=PT, kv_max_seq=64)
    prompts = [r.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in PROMPTS]
    for p in prompts:                    # compile outside the trace
        sched.generate(GenerateSpec(prompt=p, n_new=N_NEW))
    results = [None] * len(prompts)
    go = threading.Barrier(len(prompts))

    def run(i):
        go.wait()
        results[i] = sched.generate(GenerateSpec(prompt=prompts[i],
                                                 n_new=N_NEW))

    d = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(d)
    try:
        load = eng.load(batch)
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        jax.profiler.stop_trace()
    assert not any(t.is_alive() for t in threads)
    assert all(len(x.tokens) == N_NEW for x in results)
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    assert len(path) == 1
    return {"units": m.unit_names(), "pipeline": load.trace,
            "events": trace_mod.load(path[0]),
            "lines": _host_lines(path[0])}


def test_one_stage_span_per_unit(traced):
    units = traced["units"]
    n = Counter(e.name for e in traced["events"])
    for name in ("coldstart.L", "coldstart.R", "coldstart.A",
                 "coldstart.E", "coldstart.E.wait"):
        assert n[name] == len(units), name
    assert n["coldstart.load"] == 1
    assert n["coldstart.assemble"] == 1
    # each names its unit, once
    for name in ("coldstart.L", "coldstart.A", "coldstart.E",
                 "coldstart.E.wait"):
        got = [s["unit"] for rows in traced["lines"]
               for nm, _, _, s in rows if nm == name]
        assert sorted(got) == sorted(units), name


def test_one_place_span_per_block_unit(traced):
    """Each block unit's write into the stacked params is one
    ``coldstart.place`` span naming it, inside the load; the whole
    units (embed, final) are kept as applied, with no write."""
    blocks = [u for u in traced["units"] if u.startswith("block_")]
    got = [s["unit"] for rows in traced["lines"]
           for nm, _, _, s in rows if nm == "coldstart.place"]
    assert sorted(got) == blocks
    ev = traced["events"]
    load = next(e for e in ev if e.name == "coldstart.load")
    assemble = next(e for e in ev if e.name == "coldstart.assemble")
    for e in ev:
        if e.name == "coldstart.place":
            assert load.start_ns <= e.start_ns and \
                e.end_ns <= assemble.start_ns


def test_place_reader_on_the_traced_load(traced):
    """``coldstart.place_ms`` reads the load's place spans and its
    assemble span; a trace with no place span, as a program that builds
    its params by stacking gives, reads None."""
    ev = traced["events"]

    def read(events):
        t0 = min(e.start_ns for e in events) * 1e-9
        t1 = max(e.end_ns for e in events) * 1e-9
        run = harness.Run(cell=types.SimpleNamespace(config={}), seed=0,
                          seconds=1.0, w0=t0, w1=t1, records=[],
                          counters={}, trace=trace_mod.View(events),
                          trace_window=(t0, t1))
        return spec.load_module("metrics", "coldstart.place_ms").read(run)

    want = sum(e.dur_ns for e in ev
               if e.name in ("coldstart.place", "coldstart.assemble"))
    assert read(ev) == pytest.approx(want * 1e-6)
    assert read([e for e in ev if e.name != "coldstart.place"]) is None


def test_in_memory_events_match_the_profiler(traced):
    """PipelineTrace.record opens both: the same count, and the same
    lengths to within a millisecond, stage by stage in start order."""
    tr = traced["pipeline"]
    for stage in ("L", "R", "A", "E"):
        mem = sorted((e for e in tr.events if e.stage == stage),
                     key=lambda e: e.t_start)
        prof = sorted((e for e in traced["events"]
                       if e.name == f"coldstart.{stage}"),
                      key=lambda e: e.start_ns)
        assert len(mem) == len(prof) == len(traced["units"]), stage
        if stage == "R":              # four I/O threads: order may differ
            mem.sort(key=lambda e: e.duration)
            prof.sort(key=lambda e: e.dur_ns)
        for a, b in zip(mem, prof):
            assert abs(a.duration - b.dur_ns * 1e-9) < 1e-3, stage


def test_each_request_allocates_and_prefills_on_its_thread(traced):
    firsts = []
    for rows in traced["lines"]:
        for name, s, f, _ in rows:
            if name != "decode.first_token":
                continue
            inner = Counter(nm for nm, a, b, _ in rows
                            if s <= a and b <= f and nm != name)
            firsts.append(inner)
    assert len(firsts) == len(PROMPTS)
    for inner in firsts:
        assert inner["decode.kv_alloc"] == 1
        assert inner["decode.prefill"] == 1


def test_admit_lies_inside_a_step(traced):
    ev = traced["events"]
    steps = [e for e in ev if e.name == "decode.step"]
    admits = [e for e in ev if e.name == "decode.admit"]
    assert steps and len(admits) == len(steps)
    for a in admits:
        assert any(s.start_ns <= a.start_ns and a.end_ns <= s.end_ns
                   for s in steps)
    # one stepper at a time: steps never overlap
    steps.sort(key=lambda e: e.start_ns)
    assert all(a.end_ns <= b.start_ns for a, b in zip(steps, steps[1:]))
