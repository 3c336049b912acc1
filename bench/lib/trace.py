"""The device trace of a ``--trace 1`` run, and its reduction.

A :class:`Tracer` starts JAX's profiler for a span of the window (python
tracing off, so the host runs at its normal pace) and marks the two
clocks: the benchmark's ``time.monotonic()`` and the trace's own.

:func:`load` flattens the profiler's ``.xplane.pb`` into plain
:class:`Event` rows; a recorded trace committed as a test fixture is a
JSON list of the same rows.  :class:`View` answers what the metrics ask:
device busy time as a union of op intervals, each kernel's summed time,
the program executions by module name, and what the host was doing in
the device's idle gaps.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import shutil
import tempfile
import threading
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from bench.lib import window

CLOCK_MARK = "bench.clock"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME = re.compile(r"^%([^\s=]+?)(?:\.\d+)? = ")
OUT_SHAPE = re.compile(r" = \(?\w+\[([\d,]*)\]")
CONTAINERS = ("while", "conditional", "call")
HOST_EVENT_MAX_NS = 2e9           # longer host events are waits, not work


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Tracer:
    """Profile ``[w0 + start_s, w0 + start_s + span_s]`` of a run."""

    def __init__(self, start_s: float, span_s: float,
                 keep: Optional[str] = None):
        self.start_s, self.span_s, self.keep = start_s, span_s, keep
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.window: Optional[Tuple[float, float]] = None
        self.mark: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._th: Optional[threading.Thread] = None

    def start(self, w0: float):
        self._th = threading.Thread(target=self._run, args=(w0,),
                                    name="bench-tracer")
        self._th.start()

    def _run(self, w0: float):
        import jax
        try:
            time.sleep(max(0.0, w0 + self.start_s - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            t0 = time.monotonic()
            self.mark = time.monotonic()
            with jax.profiler.TraceAnnotation(CLOCK_MARK):
                pass
            time.sleep(max(0.0, t0 + self.span_s - time.monotonic()))
            t1 = time.monotonic()
            jax.profiler.stop_trace()
            self.window = (t0, t1)
        except BaseException as e:
            self.error = e

    def join(self):
        if self._th is not None:
            self._th.join()
        if self.error is not None:
            raise self.error

    def path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        if self.keep:
            os.makedirs(self.keep, exist_ok=True)
            shutil.copy(found[0], self.keep)
        return found[0]

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def load(path: str) -> List[Event]:
    """Every event of the device planes and of the host threads from a
    profiler ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name) is not None
        if not dev and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def load_json(path: str) -> List[Event]:
    """Events saved as JSON rows (plane, line, name, start_ns, dur_ns):
    a recorded trace kept as a test fixture."""
    with open(path) as f:
        return [Event(*row[:5]) for row in json.load(f)]


class View:
    """A reduced trace.  Times are seconds on the benchmark's monotonic
    clock once :meth:`align` has placed the clock mark."""

    def __init__(self, events: List[Event], mark_s: Optional[float] = None):
        self.events = events
        self.offset_s = 0.0
        if mark_s is not None:
            self.align(mark_s)
        self.devices = sorted({e.plane for e in events
                               if DEVICE_PLANE.match(e.plane)})

    def align(self, mark_s: float):
        m = [e for e in self.events if e.name == CLOCK_MARK]
        if not m:
            raise RuntimeError("no clock mark in the trace")
        self.offset_s = mark_s - m[0].start_ns * 1e-9

    def t(self, ns: float) -> float:
        return ns * 1e-9 + self.offset_s

    # ------------------------------------------------------------ device
    def ops(self, plane: Optional[str] = None) -> List[Event]:
        return [e for e in self.events if e.line == OPS_LINE
                and DEVICE_PLANE.match(e.plane)
                and (plane is None or e.plane == plane)]

    def modules(self) -> List[Event]:
        return [e for e in self.events if e.line == MODULES_LINE
                and DEVICE_PLANE.match(e.plane)]

    def busy_s(self, t0: float, t1: float) -> float:
        """Seconds in [t0, t1] in which an op ran, averaged over the
        devices that ran any."""
        per = []
        for d in self.devices:
            iv = [(self.t(e.start_ns), self.t(e.end_ns)) for e in self.ops(d)]
            if iv:
                per.append(window.union_length(iv, t0, t1))
        return sum(per) / len(per) if per else 0.0

    def kernel(self, kind: str, t0: float = float("-inf"),
               t1: float = float("inf")) -> List[Event]:
        """Op events of kind ``kind`` (a Pallas kernel's custom call is
        named after its jitted entry point, ``%decode_attention_paged.9
        = ...``) that start in [t0, t1)."""
        return [e for e in self.ops() if op_kind(e.name) == kind
                and t0 <= self.t(e.start_ns) < t1]

    def within(self, outer: Event, inner: List[Event]) -> List[Event]:
        return [e for e in inner if outer.start_ns <= e.start_ns
                and e.end_ns <= outer.end_ns]

    def module(self, name: Optional[str] = None, t0: float = float("-inf"),
               t1: float = float("inf")) -> List[Event]:
        """Program executions (of jitted function ``name``, e.g.
        ``jit_step``, when given) that lie wholly in [t0, t1]."""
        return [e for e in self.modules()
                if (name is None or e.name.split("(")[0] == name)
                and t0 <= self.t(e.start_ns) and self.t(e.end_ns) <= t1]

    def top_ops(self, t0: float, t1: float, n: int = 10
                ) -> List[List[Any]]:
        tot: Dict[str, float] = defaultdict(float)
        for e in self.ops():
            kind = op_kind(e.name)
            if kind in CONTAINERS:        # its body's ops are listed too
                continue
            s, f = max(self.t(e.start_ns), t0), min(self.t(e.end_ns), t1)
            if f > s:
                tot[kind] += f - s
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]

    # -------------------------------------------------------------- host
    def spans(self) -> List[Event]:
        return [e for e in self.events if e.name.startswith("bench.")
                and e.name != CLOCK_MARK]

    def host(self) -> List[Event]:
        """The host threads' events, the benchmark's own spans aside."""
        return [e for e in self.events if e.plane.startswith("/host:")
                and not e.name.startswith("bench.") and e.dur_ns > 0
                and e.dur_ns < HOST_EVENT_MAX_NS]

    def idle_gaps(self, t0: float, t1: float, n: int = 10,
                  min_s: float = 1e-4) -> List[List[Any]]:
        """The device's idle seconds in [t0, t1], summed by what the host
        was doing, longest first.  A gap is labelled by the innermost of
        the benchmark's ``bench.*`` spans that covers its middle, or else
        by the host event that overlaps it most (``host:<name>``: the
        program's own work, dispatch or transfer), or ``none``."""
        dev = self.devices[0] if self.devices else None
        iv = [(self.t(e.start_ns), self.t(e.end_ns)) for e in self.ops(dev)]
        spans = [(self.t(e.start_ns), self.t(e.end_ns), e.name)
                 for e in self.spans()]
        host = sorted((self.t(e.start_ns), self.t(e.end_ns), e.name)
                      for e in self.host())
        starts = [h[0] for h in host]
        reach = HOST_EVENT_MAX_NS * 1e-9
        tot: Dict[str, float] = defaultdict(float)
        for s, f in window.gaps(iv, t0, t1):
            if f - s < min_s:
                continue
            mid = 0.5 * (s + f)
            inner = [sp for sp in spans if sp[0] <= mid <= sp[1]]
            if inner:
                label = min(inner, key=lambda sp: sp[1] - sp[0])[2]
            else:
                over: Dict[str, float] = defaultdict(float)
                lo = bisect.bisect_left(starts, s - reach)
                for hs, hf, name in host[lo:bisect.bisect_right(starts, f)]:
                    if hf > s:
                        over[name] += min(hf, f) - max(hs, s)
                label = f"host:{max(over, key=over.get)[:60]}" if over \
                    else "none"
            tot[label] += f - s
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]


def op_kind(name: str) -> str:
    """An op's HLO name without its instance number: ``%fusion.12 =
    f32[512] fusion(...)`` -> ``fusion``, so that ops of one kind add
    up."""
    m = OP_NAME.match(name)
    return m.group(1) if m else re.sub(r"[.\-]\d+$", "", name)


def out_dims(name: str) -> List[int]:
    """The dimensions of an op's (first) result, from its HLO text."""
    m = OUT_SHAPE.search(name)
    return [int(d) for d in m.group(1).split(",") if d] if m else []
