"""The benchmark's modules import each other by name, as `run.py` sets
them up: put its directory on the path for these tests."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "onchip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
