#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/onchip/run.py --workload olmo-1b.chat --seed 7 \
        --seconds 30 --trace 0

from the root of a checkout on a machine holding the chips the cell
asks for.  The last line of standard output is the result: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or its
per-layer metrics with `--trace 1`), `device`, and last `checks`, each
number compared beside its limit (also the last lines of standard
error).  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

STARTED = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def _program_in_checkout() -> bool:
    """The system under test is this checkout's `src/`, never an
    installed copy."""
    try:
        import repro
    except ImportError:
        return False
    return all(pathlib.Path(p).resolve().is_relative_to(ROOT)
               for p in repro.__path__)


if __name__ == "__main__":
    if not _program_in_checkout():
        print(f"FAIL: no program under {ROOT / 'src'}", file=sys.stderr)
        sys.exit(1)
    import harness
    sys.exit(harness.main(started=STARTED))
