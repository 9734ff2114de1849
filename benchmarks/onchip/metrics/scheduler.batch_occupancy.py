"""Mean share of engine slots holding a request, read from every engine
every 50 ms through the window."""
UNIT = "%"


def read(ctx):
    xs = [a for s in ctx.window.samples for a in s["active"]]
    if not xs:
        return None
    return sum(xs) / len(xs) / ctx.engine["n_slots"] * 100.0
