"""A traffic kind added as a file of its own: ``size`` requests arrive
together every ``period_s`` seconds, sent open loop."""
from bench.lib import traffic


def plan(mix, seed, seconds):
    span = float(mix.get("lead_in_s", 0.0)) + float(seconds)
    period, size = float(mix["period_s"]), int(mix["size"])
    due = [k * period for k in range(int(span / period) + 1)
           if k * period < span for _ in range(size)]
    return traffic.requests(mix, seed, due)


def drive(prog, plan, t_start, w1):
    return traffic.drive_open(prog, plan, t_start, w1)

