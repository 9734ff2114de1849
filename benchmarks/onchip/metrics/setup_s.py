"""Process start to the opening of the measured window: weights,
deploy, warm-up (compiles included) and the lead-in traffic."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
