"""Open loop: Poisson arrivals at ``rate_rps`` from the start of the
lead-in to the window's close, each request sent at its due time whether
or not the last has finished."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench.lib import traffic


def arrivals(rate: float, span: float, seed: int, stream: int
             ) -> np.ndarray:
    """A Poisson process on [0, span) with its count fixed at
    ``rate * span``: uniform arrival times from the work's ``stream``,
    whose gaps the seed puts in another order.  Every seed offers the
    same gaps, all inside the span."""
    n = int(round(rate * span))
    t = np.sort(traffic.work_rng(stream, 1).uniform(0.0, span, n))
    gaps = np.diff(t, prepend=0.0)
    return np.cumsum(gaps[traffic.order_rng(seed, stream).permutation(n)])


def plan(mix: Dict[str, Any], seed: int, seconds: float
         ) -> List[traffic.Planned]:
    """The lead-in and the window each draw their own arrivals and
    lengths, so the window holds the same work under every seed."""
    rate = float(mix["rate_rps"])
    lead = float(mix.get("lead_in_s", 0.0))
    before = traffic.requests(mix, seed, arrivals(rate, lead, seed, 1),
                              stream=1)
    inside = traffic.requests(mix, seed,
                              lead + arrivals(rate, float(seconds), seed, 2),
                              stream=2, first=len(before))
    return before + inside


def drive(prog, plan: List[traffic.Planned], t_start: float, w1: float):
    return traffic.drive_open(prog, plan, t_start, w1)

