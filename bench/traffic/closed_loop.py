"""Closed loop: ``clients`` clients, each sending its next request when
the last one completes.  With ``cold`` the keep-alive lapses before every
request (one client), so each one is a cold start that streams every
unit from the store."""
from __future__ import annotations

from typing import Any, Dict, List

from bench.lib import traffic


def plan(mix: Dict[str, Any], seed: int, seconds: float
         ) -> List[traffic.Planned]:
    return traffic.requests(mix, seed, [0.0] * traffic.CLOSED_POOL)


def drive(prog, plan: List[traffic.Planned], t_start: float, w1: float):
    mix = prog.mix
    before = after = None
    if mix.get("cold", False):
        def before(rec):
            with prog.span("sweep"):
                prog.platform.sweep(1e12)       # the keep-alive lapses

        def after(rec):
            if rec.ok and rec.cold:
                rec.construct_s = prog.last_load_construct_s()
    # clients join one by one over the first half of the lead-in, so
    # their first prefills do not all land at once
    return traffic.drive_closed(
        prog, plan, w1, int(mix["clients"]),
        ramp_s=0.5 * float(mix.get("lead_in_s", 0.0)),
        before=before, after=after)

