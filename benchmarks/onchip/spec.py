"""What a cell is made of, found by name.

`BENCHMARK.json` at the root of the checkout names each cell's
configuration, traffic mix and chips, and each metric.  Everything that
belongs to one configuration, one traffic mix or one per-layer metric
lives in a file of its own under this directory, found by that name:

    configs/<config>.json     sizes, engine settings, limits, source,
                              and the family it names
    families/<family>.py      what is particular to a model family:
                              builder, weight scales, reference, work
    traffic/<traffic>.json    parameters of the one general generator
    metrics/<metric>.py       a reader: UNIT and read(ctx) -> float|None

so a later change adds a cell, a mix, a metric or a model family by
adding files and entries, and edits nothing that is here.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class SpecError(ValueError):
    """A name that no file answers, or a file that breaks its format."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]]      # None: every cell reporting `moves`
    moves: str = ""                     # per-layer only


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


@dataclasses.dataclass(frozen=True)
class Benchmark:
    cells: Dict[str, Cell]
    end_to_end: Dict[str, Metric]
    per_layer: Dict[str, Metric]

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                            f"there are {sorted(self.cells)}")
        return self.cells[name]

    def end_to_end_of(self, cell: str) -> List[Metric]:
        return [m for m in self.end_to_end.values()
                if m.workloads is None or cell in m.workloads]

    def per_layer_of(self, cell: str) -> List[Metric]:
        """Per-layer metrics this cell reports: those that list it, and
        those without a list whose moved metric the cell reports."""
        e2e = {m.name for m in self.end_to_end_of(cell)}
        return [m for m in self.per_layer.values()
                if (cell in m.workloads if m.workloads is not None
                    else m.moves in e2e)]


def load_benchmark(path: pathlib.Path = ROOT / "BENCHMARK.json"
                   ) -> Benchmark:
    raw = json.loads(pathlib.Path(path).read_text())
    cells = {w["name"]: Cell(w["name"], w["config"], w["traffic"],
                             int(w["chips"])) for w in raw["workloads"]}
    e2e = {m["name"]: Metric(m["name"], m["unit"], m.get("workloads"))
           for m in raw["end_to_end"]}
    per = {m["name"]: Metric(m["name"], m["unit"], m.get("workloads"),
                             m["moves"]) for m in raw["per_layer"]}
    return Benchmark(cells, e2e, per)


def _json_file(kind: str, name: str, base: pathlib.Path) -> Dict[str, Any]:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (base / kind).glob("*.json"))
        raise SpecError(f"no {kind[:-1]} {name!r} ({path}); there are "
                        f"{known}")
    data = json.loads(path.read_text())
    if data.get("name") != name:
        raise SpecError(f"{path} names itself {data.get('name')!r}")
    return data


def load_config(name: str, base: pathlib.Path = HERE) -> Dict[str, Any]:
    """configs/<name>.json: `family` (the `families/<family>.py` that
    reads it), `arch` (the model's sizes as run), `engine` (deployment
    settings), `limits` (what `correct` compares), `check` (the sample
    it compares), `source`, `reduced`, `assumed`."""
    data = _json_file("configs", name, base)
    for key in ("family", "arch", "engine", "limits", "check", "source",
                "reduced"):
        if key not in data:
            raise SpecError(f"config {name!r} has no {key!r}")
    return data


def load_traffic(name: str, base: pathlib.Path = HERE) -> Dict[str, Any]:
    """traffic/<name>.json: parameters of `traffic.generate`."""
    data = _json_file("traffic", name, base)
    if data.get("loop") not in ("open", "closed"):
        raise SpecError(f"traffic {name!r}: loop must be open or closed")
    return data


# what a family module provides; `a` is a config's `arch`, `counters`
# the window's program counters (`readers.Counters`: `get(name)`)
FAMILY = (
    "build_arch",       # (cfg) -> the program's ArchConfig
    "leaf_std",         # (path, shape) -> the scale of one weight leaf
    "logits",           # (weights, arch, tokens, pad_to, precision, rows)
                        #   -> the plain float32 reference's logits
    "decode_flops",     # (a, contexts, counters) -> the algorithm's work
    "decode_bytes",     # (a, steps, contexts, counters)
    "prefill_flops",    # (a, prompt_lens, counters)
)


def load_family(name: str, base: pathlib.Path = HERE):
    """families/<name>.py, loaded by path, once a process for each file
    (its jitted reference keeps its compiled programs)."""
    path = base / "families" / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in (base / "families").glob("*.py"))
        raise SpecError(f"no family {name!r} ({path}); there are {known}")
    mod = _family_module(name, path.resolve())
    missing = [f for f in FAMILY if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"family {name!r} ({path}) lacks {missing}")
    return mod


@functools.lru_cache(maxsize=None)
def _family_module(name: str, path: pathlib.Path):
    return _exec_module("onchip_family_", name, path)


def _exec_module(prefix: str, name: str, path: pathlib.Path):
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Reader:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


def load_reader(name: str, base: pathlib.Path = HERE) -> Reader:
    """metrics/<name>.py, loaded by path (metric names hold dots)."""
    path = base / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} ({path})")
    mod = _exec_module("onchip_metric_", name, path)
    return Reader(name, mod.UNIT, mod.read)
