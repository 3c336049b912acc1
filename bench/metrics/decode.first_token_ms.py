"""A warm request's path to its first token in the DecodeScheduler: the
mean length of the program's ``decode.first_token`` spans (page
reservation, prefill, first token on the host) that lie wholly in the
traced span (milliseconds)."""
from bench.lib import spans


def read(run):
    return spans.mean_ms(run, "decode.first_token")
