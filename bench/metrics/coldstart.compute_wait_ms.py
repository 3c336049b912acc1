"""Time the compute unit (E) waits for a unit's weights to be applied:
the mean length of the program's ``coldstart.E.wait`` spans that lie
wholly in the traced span (milliseconds a unit)."""
from bench.lib import spans


def read(run):
    return spans.mean_ms(run, "coldstart.E.wait")
