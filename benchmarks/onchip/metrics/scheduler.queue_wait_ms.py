"""Mean wait of a request from its creation at the Gateway to its first
admission to a slot, over the requests admitted inside the window, in
ms.  Read from the program's `scheduler.queue_wait` records."""
import spans

UNIT = "ms"


def read(ctx):
    waits = spans.in_window(ctx, "scheduler.queue_wait")
    if not waits:
        return None
    return sum(w.t_end - w.t_start for w in waits) / len(waits) * 1e3
