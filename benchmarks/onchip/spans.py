"""The program's own span records (`repro.obs`), read in the run's
process after the window and put on the window's clock: the readers of
`program_counter` metrics take them from here.  None where the program
keeps no such ring or the ring lost part of the window."""
from __future__ import annotations

import time
from typing import List, Optional, Tuple


def in_window(ctx, name: str,
              bounds: Optional[Tuple[float, float]] = None
              ) -> Optional[List]:
    """Records of `name` that ended inside the measured window (or inside
    `bounds` on its clock), their times moved onto the window's clock
    (`time.perf_counter`)."""
    try:
        from repro import obs
    except ImportError:                 # a program without the ring
        return None
    shift = obs.clock() - time.perf_counter()
    a, b = bounds or (ctx.window.t0, ctx.window.t1)
    got = obs.records(name, a + shift, b + shift)
    if got is None:
        return None
    return [r._replace(t_start=r.t_start - shift, t_end=r.t_end - shift)
            for r in got]
