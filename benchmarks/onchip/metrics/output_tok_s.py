"""Output tokens that arrived inside the window, over the window."""
import readers

UNIT = "tokens/s"


def read(ctx):
    w = ctx.window
    return readers.tokens_in(ctx, w.t0, w.t1) / (w.t1 - w.t0)
