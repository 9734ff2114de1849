"""The whole decode step's share of the chip's bf16 peak: FLOPs the
algorithm needs over the decode device time, in %."""
import readers

UNIT = "%"


def read(ctx):
    return readers.mfu_decode(ctx)
