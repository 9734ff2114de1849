"""Reduction of a profiler trace (`.xplane.pb`) to what the per-layer
metrics read: device busy time (the union of the intervals in which an
operation ran), device time and count per compiled program, the
operations that took most time, and the longest idle gaps labelled by
what the host was doing in them.

Device planes are `/device:TPU:<id>`; their `XLA Ops` line holds one
event per operation and `XLA Modules` one per program execution, named
`<program>(<id>)`.  Host threads live on `/host:CPU`.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from jax.profiler import ProfileData

OPS, MODULES = "XLA Ops", "XLA Modules"
HOST = "/host:CPU"
TOP = 10
OP_NAME = 100          # an op's name is its HLO text: keep the head
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]        # (start_ns, end_ns)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                          # mean over the devices read
    busy_by_device: Dict[int, float]
    programs: Dict[str, Tuple[float, int]]  # name -> (device s, runs)
    device_ops: List[Tuple[str, float]]     # top operations by time
    idle_gaps: List[Tuple[str, float]]      # longest gaps, host label

    def program(self, fragment: str) -> Tuple[float, int]:
        """Device seconds and runs of every program whose name holds
        `fragment` (summed over the devices read)."""
        s, n = 0.0, 0
        for name, (secs, runs) in self.programs.items():
            if fragment in name:
                s, n = s + secs, n + runs
        return s, n


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def _host_label(events: Sequence[Tuple[float, float, str]],
                gap: Interval) -> str:
    """The host event overlapping most of the gap (innermost wins a
    tie, as the shortest); 'host idle' where none does."""
    best, best_key = "host idle", None
    a, b = gap
    for s, e, name in events:
        ov = min(e, b) - max(s, a)
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce(data: ProfileData, window_s: float,
           devices: Sequence[int]) -> TraceSummary:
    want = set(devices)
    busy: Dict[int, float] = {}
    programs: Dict[str, List] = {}
    ops: Dict[str, float] = {}
    gaps: List[Interval] = []
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name == HOST:
            for ln in plane.lines:
                host.extend((ev.start_ns, ev.end_ns, ev.name)
                            for ev in ln.events)
            continue
        m = _DEVICE.match(plane.name)
        if not m or int(m.group(1)) not in want:
            continue
        dev = int(m.group(1))
        op_line, mod_line = _line(plane, OPS), _line(plane, MODULES)
        spans = []
        if op_line is not None:
            for ev in op_line.events:
                spans.append((ev.start_ns, ev.end_ns))
                name = ev.name[:OP_NAME]
                ops[name] = ops.get(name, 0.0) + ev.duration_ns * 1e-9
        if mod_line is not None:
            for ev in mod_line.events:
                name = _MODULE_ID.sub("", ev.name)
                acc = programs.setdefault(name, [0.0, 0])
                acc[0] += ev.duration_ns * 1e-9
                acc[1] += 1
                if op_line is None:
                    spans.append((ev.start_ns, ev.end_ns))
        merged = merge(spans)
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        gaps.extend((merged[i][1], merged[i + 1][0])
                    for i in range(len(merged) - 1))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    labelled = [(_host_label(host, g), (g[1] - g[0]) * 1e-9)
                for g in gaps[:TOP]]
    top_ops = sorted(ops.items(), key=lambda kv: kv[1], reverse=True)[:TOP]
    mean_busy = sum(busy.values()) / len(want) if want else 0.0
    return TraceSummary(window_s, mean_busy, busy,
                        {k: (v[0], v[1]) for k, v in programs.items()},
                        top_ops, labelled)


def newest_xplane(trace_dir: pathlib.Path) -> Optional[pathlib.Path]:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def reduce_dir(trace_dir: pathlib.Path, window_s: float,
               devices: Sequence[int]) -> Optional[TraceSummary]:
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    return reduce(ProfileData.from_file(str(path)), window_s, devices)
