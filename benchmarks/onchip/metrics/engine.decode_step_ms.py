"""Device time of the fused-decode program per decode step, from the
trace."""
import readers

UNIT = "ms"


def read(ctx):
    return readers.decode_step_ms(ctx)
