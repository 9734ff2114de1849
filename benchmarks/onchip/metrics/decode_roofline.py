"""Least time of the decode steps (weights and the KV of the actual
context lengths at peak bandwidth, or their FLOPs at peak compute,
whichever is longer) over their measured device time, in %."""
import readers

UNIT = "%"


def read(ctx):
    return readers.decode_roofline(ctx)
