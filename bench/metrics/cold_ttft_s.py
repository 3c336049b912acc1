"""Cold-start time to first token: mean, over every cold generation
request submitted in the window, of the time from submit to its first
token (router queue, the loading pipeline, and the first token that the
pipeline's E units produce)."""


def read(run):
    xs = [r.t_first - r.t_submit for r in run.records
          if r.ok and r.cold and run.in_window(r.t_submit)]
    return sum(xs) / len(xs) if xs else None
