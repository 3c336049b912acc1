"""Mean interval between consecutive decode steps in the window while
some request was resident (milliseconds).  A step's time is when its
tokens came back to the host, which every row of that step shares."""
import bisect

from bench.lib import window


def read(run):
    steps = sorted({round(t, 6) for r in run.records if r.ok
                    for t in r.times[1:] if run.in_window(t)})
    live = window.merge((r.times[0], r.times[-1]) for r in run.records
                        if r.ok and len(r.times) > 1)
    starts = [s for s, _ in live]

    def resident(a, b):
        i = bisect.bisect_right(starts, a) - 1
        return i >= 0 and b <= live[i][1]

    xs = [b - a for a, b in zip(steps, steps[1:]) if resident(a, b)]
    return 1e3 * sum(xs) / len(xs) if xs else None
