"""Cells, configurations, model families, mixes and metrics are found by
name, so a new one is new files and entries; BENCHMARK.json keeps to its
format."""
import json
import pathlib
import shutil
import time
import types

import harness
import jax
import numpy as np
import pytest
import readers
import reference
import spec

from repro import obs

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
        spec.load_family(cfg["family"])
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
    for sub in ("configs", "families", "traffic", "metrics"):
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


def test_a_config_without_a_family_is_an_error(tmp_path):
    base = _copy_base(tmp_path)
    cfg = json.loads((base / "configs" / "olmo-1b.json").read_text())
    del cfg["family"]
    (base / "configs" / "olmo-1b.json").write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="'family'"):
        spec.load_config("olmo-1b", base)


def test_an_unknown_family_is_an_error_that_lists_the_families(tmp_path):
    # a families dir of its own, so that a family file added to the
    # benchmark later leaves the listing this test expects unchanged
    base = tmp_path / "onchip"
    (base / "families").mkdir(parents=True)
    shutil.copy(spec.HERE / "families" / "dense.py", base / "families")
    (base / "families" / "other.py").write_text("")
    with pytest.raises(spec.SpecError, match=r"\['dense', 'other'\]"):
        spec.load_family("no-such-family", base)
    (base / "families" / "partial.py").write_text(
        "def build_arch(cfg):\n    return None\n")
    with pytest.raises(spec.SpecError, match="leaf_std"):
        spec.load_family("partial", base)


# A family that no file of the benchmark knows: logits from the
# embedding alone (position i reads token i), its decode work one
# projection a token plus what the program's `moe.route` records count.
EMBED_ONLY = '''
import numpy as np
import reference


def build_arch(cfg):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=cfg["name"], family="dense", **cfg["arch"])


def leaf_std(path, shape):
    return 0.5 if path[-1] == "embed" else 0.01


def logits(weights, arch, tokens, pad_to, precision="f32", rows=None):
    rows = np.arange(len(tokens)) if rows is None else np.asarray(rows)
    e = weights["embed"]
    x = e[np.asarray(tokens, np.int32)[rows]]
    return np.asarray(reference.mm("rd,vd->rv", x, e, precision))


def decode_flops(a, contexts, counters):
    routed = counters.get("moe.route") or []
    return 2 * a["d_model"] * a["vocab"] * len(list(contexts)) \\
        + sum(r.attrs["flops"] for r in routed)


def decode_bytes(a, steps, contexts, counters):
    return steps * 2 * a["d_model"] * a["vocab"]


def prefill_flops(a, prompt_lens, counters):
    return sum(2 * a["d_model"] * a["vocab"] for _ in prompt_lens)
'''


def test_a_family_added_as_files_is_found(tmp_path, monkeypatch):
    """A new family is a file under families/ and a config that names
    it: the builder, the weights, the reference's gaps and the work
    counts the readers take all come from it, with no other file
    edited."""
    base = _copy_base(tmp_path)
    (base / "families" / "embed-only.py").write_text(EMBED_ONLY)
    cfg = json.loads((base / "configs" / "olmo-1b.json").read_text())
    cfg.update(name="tiny-embed", family="embed-only", arch=dict(
        cfg["arch"], n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, vocab=64))
    (base / "configs" / "tiny-embed.json").write_text(json.dumps(cfg))

    cfg = spec.load_config("tiny-embed", base)
    family = spec.load_family(cfg["family"], base)
    a = cfg["arch"]
    arch = family.build_arch(cfg)
    assert (arch.name, arch.d_model, arch.vocab) == ("tiny-embed", 32, 64)
    w = harness.make_weights(family, arch, 4_000_000_007, jax.devices()[0])
    embed = np.asarray(w["embed"], np.float32)
    assert embed.shape == (64, 32)
    assert 0.4 < embed.std() < 0.6           # the family's scale
    assert np.asarray(w["layers"]["attn"]["wq"], np.float32).std() < 0.02

    # greedy tokens of the family's own forward read no gap; a token
    # altered reads one
    prompt = [3, 17, 40]
    served = [int(np.argmax(family.logits(w, a, prompt, 64)[-1]))]
    for _ in range(5):
        seq = prompt + served
        served.append(int(np.argmax(family.logits(w, a, seq, 64)[-1])))
    g, c = reference.gaps(family, w, a, prompt, served, 64, control=True)
    assert g.shape == c.shape == (6,) and g.max() == 0.0
    bad = served[:2] + [(served[2] + 1) % 64] + served[3:]
    gb, _ = reference.gaps(family, w, a, prompt, bad, 64)
    assert gb[2] > 0.0 and not gb[:2].any()

    # a work count, from the sizes and the program's counters
    rec = obs.Record("moe.route", 0.0, 0.0, "t", {"flops": 1000})
    assert family.decode_flops(a, [5, 6], {}) == 2 * 2 * 32 * 64
    assert family.decode_flops(a, [5, 6], {"moe.route": [rec]}) \
        == 2 * 2 * 32 * 64 + 1000

    # and through a reader: counters are the program's records in the
    # traced sub-window
    ring = obs.Ring()
    monkeypatch.setattr(obs, "RING", ring)
    t0 = time.perf_counter()
    shift = obs.clock() - time.perf_counter()
    for at, flops in ((1.0, 1000), (5.0, 7)):        # 5.0 is not traced
        obs.record("moe.route", t0 + at + shift, t0 + at + shift,
                   flops=flops)
    req = types.SimpleNamespace(spec=types.SimpleNamespace(prompt=prompt),
                                times=[t0 + 0.6, t0 + 0.7, t0 + 0.8])
    window = types.SimpleNamespace(t0=t0, t1=t0 + 10.0, records=[req],
                                   trace_window=(t0 + 0.5, t0 + 2.0))
    trace = types.SimpleNamespace(program=lambda fragment: (1e-3, 2))
    ctx = types.SimpleNamespace(family=family, arch=a, window=window,
                                trace=trace, engine={"decode_block": 1},
                                peaks={"bf16_flops_per_s": 1e12})
    want = (2 * 2 * 32 * 64 + 1000) / 1e-3 / 1e12 * 100.0
    assert readers.mfu_decode(ctx) == pytest.approx(want)
