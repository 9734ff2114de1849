"""The whole prefill program's share of the chip's bf16 peak, FLOPs
counted on real prompt tokens, in %."""
import readers

UNIT = "%"


def read(ctx):
    return readers.mfu_prefill(ctx)
