"""Host-sync-in-hot-path checker.

The decode/prefill hot path's perf contract is "at most two jitted
dispatches and one host sync per step" (CI-gated by bench counters
since PR 5).  The bench can only count syncs it executes; this checker
pins the *sites*: every expression that forces a device->host transfer
(``jax.device_get``, ``.item()``, ``.tolist()``, ``np.asarray``/
``np.array`` on device values, ``float()``/``int()``/``bool()`` of a
name or attribute, ``.block_until_ready()``) reachable from the engine
step entry point over the name-based call graph.

Each sanctioned sync is waived individually in `analysis_baseline.json`
(keyed by function + pattern + occurrence), so adding a *second*
``device_get`` to `_decode_block` surfaces as a new unwaived violation
even if the bench workload happens not to hit it.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analysis.core import (Checker, FunctionInfo, ProjectIndex,
                                 Violation, call_name, call_receiver)

# (class, method) roots of the fused decode/prefill paths
DEFAULT_ENTRIES: Tuple[Tuple[str, str], ...] = (
    ("InferenceEngine", "step"),
)

_NP_MODULES = {"np", "numpy", "onp"}


def _sync_pattern(call: ast.Call) -> Optional[str]:
    """Pattern slug when `call` forces a host sync, else None."""
    name = call_name(call)
    if name is None:
        return None
    recv = call_receiver(call)
    if name == "device_get":
        return "device_get"
    if name in ("item", "tolist", "block_until_ready") and not call.args:
        return name
    if name in ("asarray", "array") and recv is not None \
            and recv[-1] in _NP_MODULES:
        # literals are host-side already; anything else may be a tracer
        if call.args and not isinstance(call.args[0],
                                        (ast.Constant, ast.List,
                                         ast.Tuple, ast.ListComp)):
            return f"np.{name}"
        return None
    if name in ("float", "int", "bool") and recv is None and call.args:
        # float(self.x) / int(done) force concretization when the value
        # is device-resident; float(len(..)) and literals don't
        if isinstance(call.args[0], (ast.Name, ast.Attribute)):
            return name
    return None


class HotPathSyncChecker(Checker):
    rule = "hot-path-sync"

    def __init__(self,
                 entries: Sequence[Tuple[str, str]] = DEFAULT_ENTRIES):
        self.entries = tuple(entries)

    def check(self, index: ProjectIndex) -> List[Violation]:
        # reachability over the name-based call graph from the entries
        roots: List[FunctionInfo] = []
        for cls, meth in self.entries:
            fi = index.by_class.get(cls, {}).get(meth)
            if fi is not None:
                roots.append(fi)
        reached: Dict[str, FunctionInfo] = {}
        work = list(roots)
        while work:
            fi = work.pop()
            if fi.uid in reached:
                continue
            reached[fi.uid] = fi
            for node in ast.walk(fi.node):
                if isinstance(node, ast.Call):
                    for target in index.resolve_call(node, fi.cls,
                                                     fi.module):
                        if target.uid not in reached:
                            work.append(target)

        out: List[Violation] = []
        for uid in sorted(reached):
            fi = reached[uid]
            counts: Dict[str, int] = {}
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                pattern = _sync_pattern(node)
                if pattern is None:
                    continue
                n = counts.get(pattern, 0)
                counts[pattern] = n + 1
                out.append(Violation(
                    self.rule, fi.module.rel, node.lineno, fi.qualname,
                    f"{pattern} site reachable from the engine step hot "
                    f"path ({ast.unparse(node)[:60]}) — forces a "
                    f"device->host sync",
                    detail=f"{pattern}#{n}"))
        return out


def reachable_functions(index: ProjectIndex,
                        entries: Sequence[Tuple[str, str]] = DEFAULT_ENTRIES
                        ) -> Set[str]:
    """Qualnames reachable from the hot-path entries (for tests)."""
    checker = HotPathSyncChecker(entries)
    roots = [index.by_class.get(c, {}).get(m) for c, m in checker.entries]
    reached: Set[str] = set()
    work = [fi for fi in roots if fi is not None]
    seen: Set[str] = set()
    while work:
        fi = work.pop()
        if fi.uid in seen:
            continue
        seen.add(fi.uid)
        reached.add(fi.qualname)
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Call):
                for target in index.resolve_call(node, fi.cls,
                                                 fi.module):
                    if target.uid not in seen:
                        work.append(target)
    return reached
