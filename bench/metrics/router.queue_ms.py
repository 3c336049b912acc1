"""Router and InstancePool wait: the generator's lag behind the schedule
plus ``Response.queue_s`` (router queue, pool wait), mean over the
requests due in the window (milliseconds)."""


def read(run):
    xs = [(r.t_submit - r.arrival) + r.queue_s for r in run.records
          if r.ok and run.in_window(r.arrival)]
    return 1e3 * sum(xs) / len(xs) if xs else None
