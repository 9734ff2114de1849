"""As mfu.prefill, for the long-document cell."""
import readers

UNIT = "%"


def read(ctx):
    return readers.mfu_prefill(ctx)
