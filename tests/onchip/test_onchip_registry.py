"""Cells, configurations, mixes and metrics are found by name, so a new
one is new files and entries; BENCHMARK.json keeps to its format."""
import json
import pathlib
import shutil

import pytest
import spec

ROOT = spec.ROOT
RAW = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_in_benchmark_json_has_its_file():
    bench = spec.load_benchmark()
    for cell in bench.cells.values():
        spec.load_config(cell.config)
        spec.load_traffic(cell.traffic)
    for m in list(bench.end_to_end.values()) + list(bench.per_layer.values()):
        reader = spec.load_reader(m.name)
        assert reader.unit == m.unit, m.name
    for c in RAW["configs"]:
        assert (ROOT / c["file"]).is_file()
        cfg = spec.load_config(c["name"])
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # a key changed from the source is stated in the file as run
        for key in c["reduced"]:
            assert key in cfg or key in cfg["arch"], (c["name"], key)


def test_each_cell_reports_what_its_layer_metrics_move():
    bench = spec.load_benchmark()
    for name in bench.cells:
        e2e = {m.name for m in bench.end_to_end_of(name)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = bench.per_layer_of(name)
        assert per
        for m in per:
            assert m.moves in e2e, (name, m.name)


def test_benchmark_json_keeps_to_its_format():
    assert set(RAW) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for p in RAW["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= RAW["run_seconds"] <= 51
    for m in RAW["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in RAW["end_to_end"] + RAW["per_layer"]]
    assert len(names) == len(set(names))
    four = [w for w in RAW["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(RAW["workloads"]) // 2)


def _copy_base(tmp_path: pathlib.Path) -> pathlib.Path:
    base = tmp_path / "onchip"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(spec.HERE / sub, base / sub)
    return base


def test_a_mix_config_and_metric_added_as_files_are_found(tmp_path):
    base = _copy_base(tmp_path)
    cfg = json.loads((base / "configs" / "olmo-1b.json").read_text())
    cfg["name"] = "olmo-1b-wide"
    cfg["engine"] = dict(cfg["engine"], n_slots=8)
    (base / "configs" / "olmo-1b-wide.json").write_text(json.dumps(cfg))
    mix = dict(spec.load_traffic("chat"), name="chat-burst", rate_per_s=2.0)
    (base / "traffic" / "chat-burst.json").write_text(json.dumps(mix))
    (base / "metrics" / "client.sent.py").write_text(
        'UNIT = "requests"\n\n\ndef read(ctx):\n'
        '    return float(len(ctx.window.records))\n')
    raw = dict(RAW, workloads=RAW["workloads"] + [
        {"name": "olmo-1b-wide.chat-burst", "config": "olmo-1b-wide",
         "traffic": "chat-burst", "chips": 1, "why": "a test cell"}],
        per_layer=RAW["per_layer"] + [
        {"name": "client.sent", "unit": "requests", "better": "higher",
         "source": "host_clock", "layer": "load generator",
         "moves": "output_tok_s"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    bench = spec.load_benchmark(tmp_path / "BENCHMARK.json")
    cell = bench.cell("olmo-1b-wide.chat-burst")
    assert spec.load_config(cell.config, base)["engine"]["n_slots"] == 8
    assert spec.load_traffic(cell.traffic, base)["rate_per_s"] == 2.0
    reader = spec.load_reader("client.sent", base)

    class W:
        records = [1, 2, 3]

    class Ctx:
        window = W()

    assert reader.read(Ctx()) == 3.0
    # a per-layer metric with no cell list goes to every cell that
    # reports what it moves
    assert "client.sent" in {m.name for m in bench.per_layer_of(
        "phi4-mini.longdoc")}
    assert "client.sent" not in {m.name for m in bench.per_layer_of(
        "olmo-1b.chat")}


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_config("no-such-model")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.load_reader("no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.load_benchmark().cell("no.such.cell")
