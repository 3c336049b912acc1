"""95th percentile over every inter-token gap that ends in the window,
of every request (milliseconds)."""
from bench.lib import window


def read(run):
    xs = [b - a for r in run.records if r.ok
          for a, b in zip(r.times, r.times[1:]) if run.in_window(b)]
    return 1e3 * window.percentile(xs, 95) if xs else None
