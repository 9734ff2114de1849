"""repro.analysis: each seeded fixture violation is flagged, the clean
fixture passes, the CLI gate exits nonzero correctly, the shipped source
tree is clean against its baseline, and the runtime LockOrderTracker
agrees with the static hierarchy under a pump+cancel+migration soak."""
import json
import pathlib

import pytest

from repro.analysis import (Baseline, HotPathSyncChecker, LockOrderChecker,
                            LockOrderTracker, MutableDefaultChecker,
                            RefcountChecker, SharedStateChecker,
                            TrackedLock, allowed_edges, run_checkers)
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.locks import static_edges
from repro.api import Gateway, StreamEventType
from repro.cluster import BackendNode, Fleet
from repro.configs import ARCHS
from repro.core import (ModelCatalog, ReplicaInfo, ReplicaKey,
                        SDAIController)
from repro.serving import SamplingParams

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "analysis"
SRC = pathlib.Path(__file__).parents[1] / "src" / "repro"
MODEL = "olmo-1b-reduced"


def _run(checker, name):
    path = FIXTURES / name
    return run_checkers([path], [checker], root=FIXTURES)


# ---------------- seeded fixtures ---------------------------------- #
def test_lock_inversion_fixture_flagged():
    vs = _run(LockOrderChecker(), "fx_lock_inversion.py")
    assert any(v.rule == "lock-order" and "rebalance" in v.symbol
               for v in vs), vs


def test_unguarded_state_fixture_flagged():
    vs = _run(SharedStateChecker(), "fx_unguarded_state.py")
    assert any(v.rule == "shared-state" and v.symbol == "Counter.total"
               and v.detail == "write" for v in vs), vs


def test_mutable_default_fixture_flagged():
    vs = _run(MutableDefaultChecker(), "fx_mutable_default.py")
    assert any(v.rule == "mutable-default" and "collect" in v.symbol
               for v in vs), vs


def test_hotpath_item_fixture_flagged():
    vs = _run(HotPathSyncChecker(), "fx_hotpath_item.py")
    assert any(v.rule == "hot-path-sync" and "step" in v.symbol
               and v.detail.startswith("item") for v in vs), vs


def test_refcount_leak_fixture_flagged():
    vs = _run(RefcountChecker(), "fx_refcount_leak.py")
    assert any(v.rule == "refcount-pairing" and "put" in v.symbol
               for v in vs), vs


def test_calls_bind_to_their_module_before_same_named_functions(tmp_path):
    """`obs.record()` binds to `repro/obs.py`'s `record`, and a bare
    `record()` inside that module to its own; neither reaches another
    class's `record`, whose `float()` would count as a hot-path sync."""
    (tmp_path / "repro").mkdir()
    (tmp_path / "repro" / "obs.py").write_text(
        "def record(x):\n    return x\n\n\n"
        "def span(x):\n    return record(x)\n")
    (tmp_path / "perf.py").write_text(
        "class PerfModel:\n    def record(self, x):\n"
        "        return float(x)\n")
    (tmp_path / "engine.py").write_text(
        "from repro import obs\n\n\nclass InferenceEngine:\n"
        "    def step(self):\n        obs.record(1)\n"
        "        return obs.span(2)\n")
    assert run_checkers([tmp_path], [HotPathSyncChecker()],
                        root=tmp_path) == []
    # the same call through an unknown receiver still reaches every
    # `record`, as before
    (tmp_path / "engine.py").write_text(
        "class InferenceEngine:\n    def step(self):\n"
        "        return self.stats.record(1)\n")
    vs = run_checkers([tmp_path], [HotPathSyncChecker()], root=tmp_path)
    assert [v.symbol for v in vs] == ["PerfModel.record"], vs


def test_clean_fixture_passes_every_checker():
    checkers = [LockOrderChecker(), SharedStateChecker(),
                HotPathSyncChecker(), MutableDefaultChecker(),
                RefcountChecker()]
    vs = run_checkers([FIXTURES / "fx_clean.py"], checkers,
                      root=FIXTURES)
    assert vs == []


# ---------------- CLI gate ----------------------------------------- #
def test_cli_exits_2_on_each_seeded_violation():
    for name in ("fx_lock_inversion.py", "fx_unguarded_state.py",
                 "fx_mutable_default.py", "fx_hotpath_item.py",
                 "fx_refcount_leak.py"):
        rc = analysis_main([str(FIXTURES / name),
                            "--no-baseline", "--check"])
        assert rc == 2, name


def test_cli_exits_0_on_clean_fixture():
    assert analysis_main([str(FIXTURES / "fx_clean.py"),
                          "--no-baseline", "--check"]) == 0


def test_cli_waiver_lifecycle(tmp_path):
    """write-baseline absorbs with TODO reasons (exit 3 under --check
    until a human explains each one), then filled reasons gate green."""
    fx = str(FIXTURES / "fx_mutable_default.py")
    b = tmp_path / "baseline.json"
    assert analysis_main([fx, "--baseline", str(b),
                          "--write-baseline"]) == 0
    assert analysis_main([fx, "--baseline", str(b), "--check"]) == 3
    data = json.loads(b.read_text())
    for w in data["waivers"]:
        w["reason"] = "fixture: intentionally seeded"
    b.write_text(json.dumps(data))
    assert analysis_main([fx, "--baseline", str(b), "--check"]) == 0


def test_stale_waiver_reported(tmp_path, capsys):
    b = tmp_path / "baseline.json"
    Baseline({"mutable-default::gone.py::f::arg:x": "was fixed"}).save(b)
    assert analysis_main([str(FIXTURES / "fx_clean.py"),
                          "--baseline", str(b), "--check"]) == 0
    assert "stale" in capsys.readouterr().out


# ---------------- shipped tree ------------------------------------- #
def test_src_tree_clean_against_baseline(monkeypatch):
    monkeypatch.chdir(pathlib.Path(__file__).parents[1])
    assert analysis_main(["--check"]) == 0


def test_static_lock_edges_within_hierarchy():
    mods = [SRC / "cluster" / "node.py", SRC / "serving" / "engine.py",
            SRC / "serving" / "scheduler.py", SRC / "api" / "runtime.py",
            SRC / "api" / "http" / "server.py",
            SRC / "core" / "controller.py", SRC / "api" / "gateway.py"]
    edges = static_edges([str(m) for m in mods])
    assert edges <= allowed_edges(), edges - allowed_edges()


# ---------------- runtime tracker ---------------------------------- #
def test_tracker_flags_inverted_acquisition():
    import threading
    tr = LockOrderTracker()
    sched = TrackedLock(threading.Lock(), "scheduler", tr)
    node = TrackedLock(threading.RLock(), "node", tr)
    with sched:
        with node:                      # scheduler -> node: inversion
            pass
    assert len(tr.violations) == 1
    v = tr.violations[0]
    assert (v.held_level, v.acquired_level) == ("scheduler", "node")
    assert ("scheduler", "node") in tr.disallowed_edges()


def test_tracker_canonical_and_reentrant_are_clean():
    import threading
    tr = LockOrderTracker()
    node = TrackedLock(threading.RLock(), "node", tr)
    inst = TrackedLock(threading.RLock(), "instance", tr)
    sched = TrackedLock(threading.Lock(), "scheduler", tr)
    with node:
        with node:                      # RLock re-entry: exempt
            with inst:
                with sched:
                    pass
    assert tr.violations == []
    assert tr.disallowed_edges() == set()
    assert tr.acquisitions > 0


def _pinned_stack(param_store, n_nodes=2, n_slots=2, max_len=48):
    cfg = ARCHS["olmo-1b"].reduced()
    fleet = Fleet([BackendNode(f"n{i}", "v5e-1", param_store=param_store)
                   for i in range(n_nodes)])
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog)
    ctrl.discover()
    for node in fleet.nodes.values():
        inst = node.deploy(cfg, n_slots=n_slots, max_len=max_len)
        ctrl.replicas.add(ReplicaInfo(
            ReplicaKey(node.node_id, inst.instance_id),
            cfg.name, "", n_slots, max_len, inst.bytes))
    return fleet, ctrl


def test_tracker_zero_violations_under_soak(param_store,
                                            lock_order_tracker):
    """Background pumps + a cancel + a mid-stream migration, with the
    session tracker live the whole time: the actual acquisition order
    never leaves the static hierarchy."""
    tr = lock_order_tracker
    before = len(tr.violations)
    fleet, ctrl = _pinned_stack(param_store, n_nodes=2)
    gw = Gateway(ctrl)
    gw.start()
    try:
        handles = [gw.submit(MODEL, [3, 1, 4, i], SamplingParams(
            max_tokens=8), tenant=f"t{i % 2}") for i in range(4)]
        handles[0].cancel()
        it = handles[1].stream()
        ev = next(it)
        while ev.type is not StreamEventType.TOKEN:
            ev = next(it)
        fleet.fail_node(handles[1].internal.node)    # migrate mid-stream
        for ev in it:
            pass
        assert handles[1].response.ok
        for h in handles[2:]:
            h.result(timeout_s=60)
    finally:
        gw.stop(timeout_s=10.0)
    assert tr.violations[before:] == [], \
        "\n".join(v.render() for v in tr.violations[before:])
    assert tr.disallowed_edges() == set()
    assert tr.acquisitions > 0


def test_tracker_install_is_exclusive(lock_order_tracker):
    from repro.analysis import install
    with pytest.raises(RuntimeError):
        install(LockOrderTracker())     # conftest already installed one
