"""The MiniLoader's layer construction (the pipeline's L events,
``work_by_stage()["L"]``), seconds per load, mean over the cold starts
submitted in the window."""


def read(run):
    xs = [r.construct_s for r in run.records
          if r.ok and r.cold and r.construct_s is not None
          and run.in_window(r.t_submit)]
    return sum(xs) / len(xs) if xs else None
