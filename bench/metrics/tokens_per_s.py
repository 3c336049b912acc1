"""Output tokens emitted inside the window, over the window's seconds."""


def read(run):
    n = sum(1 for r in run.records if r.ok for t in r.times
            if run.in_window(t))
    return n / (run.w1 - run.w0) if n else None
