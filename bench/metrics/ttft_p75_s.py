"""75th percentile, over all requests due in the window, of first-token
time minus the scheduled arrival.  A request that failed, was refused or
gave no token counts as a miss, at the longest wait the run allowed.
The 75th is the highest percentile with ten requests beyond it at the
chat cell's 41 requests a window."""
from bench.lib import harness, window


def read(run):
    give_up = run.w1 + harness.WAIT_PAST_CLOSE_S
    xs = [(r.t_first if r.ok else give_up) - r.arrival
          for r in run.records if run.in_window(r.arrival)]
    return window.percentile(xs, 75) if xs else None
