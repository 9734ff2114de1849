"""Device time of the bucketed-prefill program per 1000 prompt tokens
admitted, from the trace."""
import readers

UNIT = "ms"


def read(ctx):
    return readers.prefill_ms_per_1k(ctx)
