"""The loading pipeline's utilization (``PipelineTrace.utilization()``:
merged L/A/E busy time over the load's time), mean over the cold starts
submitted in the window, as a percentage."""


def read(run):
    xs = [r.utilization for r in run.records
          if r.ok and r.cold and r.utilization is not None
          and run.in_window(r.t_submit)]
    return 100.0 * sum(xs) / len(xs) if xs else None
