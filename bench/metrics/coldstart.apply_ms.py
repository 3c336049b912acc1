"""Weight application (the pipeline's A stage: placing one unit's
weights on the device) per unit: the mean length of the program's
``coldstart.A`` spans that lie wholly in the traced span
(milliseconds)."""
from bench.lib import spans


def read(run):
    return spans.mean_ms(run, "coldstart.A")
