"""Requests the Service Frontend placed on its busiest replica over the
mean per replica, inside the window (1 is even).  Read from the
program's accepted `frontend.route` records; every replica of the run
counts, those sent nothing too."""
import spans

UNIT = "ratio"


def read(ctx):
    routes = spans.in_window(ctx, "frontend.route")
    if routes is None:
        return None
    counts = dict.fromkeys(ctx.replicas, 0)
    for r in routes:
        node = r.attrs.get("node")
        if r.attrs.get("ok") and node in counts:
            counts[node] += 1
    total = sum(counts.values())
    if not total:
        return None
    return max(counts.values()) / (total / len(counts))
