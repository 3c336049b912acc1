"""Rows the DecodeScheduler's batched step served per step: tokens that
decode steps emitted in the window over the decode steps the platform
counted there."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    n = sum(1 for r in run.records if r.ok for t in r.times[1:]
            if run.in_window(t))
    return n / steps if steps and n else None
