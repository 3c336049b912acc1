"""What every traffic kind shares.  A mix is a JSON file of parameters,
``bench/traffic/<mix>.json``; its ``kind`` names the module that plans
and drives it, ``bench/traffic/<kind>.py``:

    plan(mix, seed, seconds) -> [Planned]   the requests, in order
    drive(prog, plan, t_start, w1) -> [Record]
                                            send them, wait for each
    prompt_tokens(mix, seed, index, n, vocab, warm_up) -> int32 (n,)
                                            optional: the tokens, where
                                            :func:`prompt_tokens` will
                                            not do (shared prefixes)

The parameters the kinds here read:

    rate_rps      open loop: mean arrival rate (Poisson)
    clients       closed loop: clients that each send their next request
                  when the last one completes
    cold          closed loop: the keep-alive lapses before every
                  request, so each one is a cold start
    lead_in_s     traffic before the window opens, not counted
    prompt        {"median", "sigma", "min", "max", "menu", "round"}
    output        the same, for the number of new tokens
    temperature   0 -> greedy
    trace_start_s, trace_s
                  the traced span of a ``--trace 1`` run, from the
                  window's start

Lengths are lognormal, clipped, then rounded to a menu ("up": the least
menu entry at or above; "nearest": nearest in log space).  The requests'
lengths and arrival gaps are plain independent draws from streams that
the seed does not touch; the seed draws the order they come in and the
prompt tokens.  So every seed offers the same work, in another order,
and the spread between runs is the system's, not the sampler's.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
import threading
import time
from typing import Any, Dict, List

import numpy as np

CLOSED_POOL = 4096          # requests a closed loop draws from, in order
WAIT_PAST_CLOSE_S = 60.0


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    n_prompt: int
    n_new: int
    due: float = 0.0          # open loop: seconds after the traffic starts


def work_rng(*stream: int):
    """A stream that draws the mix's work, the same under every seed."""
    return np.random.default_rng([0, *stream])


def order_rng(seed: int, *stream: int):
    """A stream that draws the order of the work: the seed's."""
    return np.random.default_rng([seed, 1, *stream])


def _round(x: float, menu: List[int], how: str) -> int:
    menu = sorted(menu)
    if how == "up":
        i = bisect.bisect_left(menu, x)
        return menu[min(i, len(menu) - 1)]
    if how == "nearest":
        return min(menu, key=lambda m: abs(math.log(m) - math.log(x)))
    raise ValueError(f"rounding {how!r}")


def lengths(dist: Dict[str, Any], n: int, rng) -> List[int]:
    """``n`` independent draws of the clipped, rounded lognormal
    ``dist``."""
    sigma = float(dist.get("sigma", 0.0))
    xs = float(dist["median"]) * np.exp(sigma * rng.standard_normal(n))
    xs = np.clip(xs, dist.get("min", 0), dist.get("max", np.inf))
    if "menu" in dist:
        return [_round(float(x), dist["menu"], dist.get("round", "up"))
                for x in xs]
    return [int(round(float(x))) for x in xs]


def requests(mix: Dict[str, Any], seed: int, due: List[float],
             stream: int = 0, first: int = 0) -> List[Planned]:
    """One request per entry of ``due``, numbered from ``first``: lengths
    drawn from the work's ``stream``, put in an order the seed draws."""
    n = len(due)
    rng = order_rng(seed, stream)
    prompts = lengths(mix["prompt"], n, work_rng(stream, 2))
    outputs = lengths(mix["output"], n, work_rng(stream, 3))
    p, o = rng.permutation(n), rng.permutation(n)
    return [Planned(first + i, prompts[p[i]], outputs[o[i]], float(due[i]))
            for i in range(n)]


def prompt_tokens(seed: int, index: int, n: int, vocab: int,
                  warm_up: bool = False) -> np.ndarray:
    """Uniform random tokens of request ``index`` (of the warm-up's own
    requests with ``warm_up``): a function of the seed alone."""
    r = np.random.default_rng([seed, 4 if warm_up else 2, index])
    return r.integers(0, vocab, (n,), dtype=np.int64).astype(np.int32)


# ------------------------------------------------------------- drivers
def drive_open(prog, plan: List[Planned], t_start: float, w1: float):
    """Send each planned request at its due time, up to the window's
    close; a request refused at admission is a miss."""
    records, futs = [], []
    for p in plan:
        due = t_start + p.due
        if due >= w1:
            break
        with prog.span("generator.sleep"):
            time.sleep(max(0.0, due - time.monotonic()))
        rec = prog.request(p.n_prompt, p.n_new, p.index, due=due)
        try:
            fut = prog.submit(rec)
        except Exception as e:            # refused at admission: a miss
            rec.error = f"{type(e).__name__}: {e}"
            fut = None
        records.append(rec)
        futs.append(fut)
    with prog.span("drain"):
        for rec, fut in zip(records, futs):
            if fut is not None:
                prog.finish(rec, fut, max(1.0, w1 + WAIT_PAST_CLOSE_S
                                          - time.monotonic()))
    return records


def drive_closed(prog, plan: List[Planned], w1: float, clients: int,
                 ramp_s: float = 0.0, before=None, after=None):
    """``clients`` threads, each sending the plan's next request when
    its last one completes, until the window closes.  They join one by
    one over ``ramp_s``.  ``before(rec)`` runs before each submission,
    ``after(rec)`` once it has finished."""
    it = iter(plan)
    lock = threading.Lock()
    records, errors = [], []

    def client(start: float):
        try:
            time.sleep(max(0.0, start - time.monotonic()))
            while time.monotonic() < w1:
                with lock:
                    p = next(it, None)
                if p is None:
                    return
                rec = prog.request(p.n_prompt, p.n_new, p.index)
                if before is not None:
                    before(rec)
                fut = prog.submit(rec)
                with prog.span("client.wait"):
                    prog.finish(rec, fut, max(1.0, w1 + WAIT_PAST_CLOSE_S
                                              - time.monotonic()))
                if after is not None:
                    after(rec)
                with lock:
                    records.append(rec)
        except BaseException as e:
            errors.append(e)

    t0 = time.monotonic()
    threads = [threading.Thread(target=client,
                                args=(t0 + ramp_s * i / clients,),
                                name=f"bench-client-{i}")
               for i in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return sorted(records, key=lambda r: r.t_submit)
