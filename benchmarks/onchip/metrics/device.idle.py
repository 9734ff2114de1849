"""Share of the traced sub-window in which no operation ran on the
device (averaged over the chips used), in %."""
import readers

UNIT = "%"


def read(ctx):
    return readers.device_idle(ctx)
