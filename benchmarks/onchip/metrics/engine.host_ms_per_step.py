"""The serving thread's own host work per engine step, in ms: the mean,
over the engine steps that lie in the window but outside the traced
sub-window (so it reads the cost with the profiler off), of the step's
time less its waits for the device (`engine.prefill.sync`,
`engine.decode.sync`).  Read from the program's span records."""
import spans

UNIT = "ms"
SYNCS = ("engine.prefill.sync", "engine.decode.sync")


def read(ctx):
    steps = spans.in_window(ctx, "engine.step")
    syncs = [spans.in_window(ctx, name) for name in SYNCS]
    if steps is None or None in syncs:
        return None
    tw = ctx.window.trace_window
    steps = [s for s in steps if s.t_start >= ctx.window.t0
             and (tw is None or s.t_end < tw[0] or s.t_start > tw[1])]
    if not steps:
        return None
    # a step's spans carry its node and step number, on its thread
    wait = {}
    for s in (r for part in syncs for r in part):
        key = (s.thread, s.attrs.get("node"), s.attrs.get("step"))
        wait[key] = wait.get(key, 0.0) + (s.t_end - s.t_start)
    host = [(s.t_end - s.t_start)
            - wait.get((s.thread, s.attrs.get("node"), s.attrs.get("step")),
                       0.0) for s in steps]
    return sum(host) / len(host) * 1e3
