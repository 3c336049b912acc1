"""The program's own host spans in a traced run.

The program opens ``jax.profiler.TraceAnnotation`` spans where its work
happens (``coldstart.A``, ``decode.prefill``, ...).  They land in the
profiler's trace beside the device's ops, on the same clock.  The
profiler drops a span that straddles its start or stop, so the readers
count only spans that lie wholly in the traced span.  A program without
such spans gives these queries nothing, and its readers ``None``.
"""
from __future__ import annotations

from typing import List, Optional

HOST = "/host:"


def wholly_in(run, name: str) -> List:
    """The host spans called ``name`` that lie wholly in the traced
    span."""
    tr = run.trace
    t0, t1 = run.trace_window
    return [e for e in tr.events
            if e.name == name and e.plane.startswith(HOST)
            and t0 <= tr.t(e.start_ns) and tr.t(e.end_ns) <= t1]


def mean_ms(run, name: str) -> Optional[float]:
    """Mean length of the spans called ``name`` (milliseconds)."""
    if run.trace is None:
        return None
    xs = wholly_in(run, name)
    return 1e-6 * sum(e.dur_ns for e in xs) / len(xs) if xs else None


def overlap_ns(a, b) -> float:
    """Nanoseconds in which events ``a`` and ``b`` both run."""
    return max(0.0, min(a.end_ns, b.end_ns) - max(a.start_ns, b.start_ns))


def inside(outer, inner: List) -> List:
    """The events of ``inner`` that lie wholly in ``outer``."""
    return [e for e in inner
            if outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns]
