"""Time a cold load spends turning its applied units into the
steady-state params: for each ``coldstart.load`` span wholly in the
traced span, its ``coldstart.place`` spans (each block unit's in-place
write into the stacked params) summed with its ``coldstart.assemble``
span; the mean over those loads (milliseconds).  A program that opens
no ``coldstart.place`` span gives None."""
from bench.lib import spans


def read(run):
    if run.trace is None:
        return None
    places = spans.wholly_in(run, "coldstart.place")
    assembles = spans.wholly_in(run, "coldstart.assemble")
    per_load = []
    for load in spans.wholly_in(run, "coldstart.load"):
        mine = spans.inside(load, places)
        if mine:
            per_load.append(sum(e.dur_ns for e in
                                mine + spans.inside(load, assembles)))
    return 1e-6 * sum(per_load) / len(per_load) if per_load else None
