"""A whole run of a cell, on the CPU at a small size: the harness's look
for a chip is skipped, the rest runs as on the chip (seeded weights, the
controller's deploy, warm-up, the Gateway under an open or closed loop,
the comparison with the reference).  A sound run is correct; a run whose
timed path alters a token where it is produced, or never delivers part
of an answer, is not, and neither is the fp8 control put in the
program's place."""
import dataclasses
import json
import shutil
import time

import pytest
import harness
import spec

from repro.serving.request import Request

TINY = {"name": "tiny", "source": "test", "reduced": [],
        "arch": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                 "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 256,
                 "norm": "rms", "act": "swiglu", "tie_embeddings": True,
                 "rope_theta": 10000.0, "dtype": "bf16"},
        "engine": {"n_slots": 4, "max_len": 48, "page_size": 16,
                   "decode_block": 4},
        # served bf16 tokens read about 1e-4 here (test_onchip_reference)
        "limits": {"widest_logit_gap": 0.005},
        "check": {"sample_tokens": 40, "max_requests": 4}}
CHAT = {"name": "tinychat", "loop": "open", "rate_per_s": 20, "lead_s": 0.5,
        "prompt": {"median": 10, "sigma": 0.5, "min": 4, "max": 16},
        "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
        "temperature": 0.0}
DOC = dict(CHAT, name="tinydoc", loop="closed", clients_per_slot=1,
           block=4, prompt={"median": 24, "sigma": 0.2, "min": 17,
                            "max": 32})


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    base = tmp_path_factory.mktemp("bench")
    for d in ("configs", "traffic"):
        (base / d).mkdir()
    shutil.copytree(spec.HERE / "metrics", base / "metrics")
    (base / "configs" / "tiny.json").write_text(json.dumps(TINY))
    for mix in (CHAT, DOC):
        (base / "traffic" / f"{mix['name']}.json").write_text(
            json.dumps(mix))
    real = spec.load_benchmark()
    # the tiny cells report what the chat and long-document cells do
    as_cell = {"olmo-1b.chat": "tiny.chat", "phi4-mini.longdoc": "tiny.doc"}

    def renamed(m):
        if m.workloads is None:
            return m
        return dataclasses.replace(
            m, workloads=[as_cell[w] for w in m.workloads if w in as_cell])

    b = spec.Benchmark(
        cells={"tiny.chat": spec.Cell("tiny.chat", "tiny", "tinychat", 1),
               "tiny.doc": spec.Cell("tiny.doc", "tiny", "tinydoc", 1)},
        end_to_end={k: renamed(m) for k, m in real.end_to_end.items()},
        per_layer={k: renamed(m) for k, m in real.per_layer.items()})
    return b, base


def _run(bench, cell, seed=4_000_000_001, control=False):
    b, base = bench
    return harness.run_cell(cell, seed, 2.0, False,
                            started=time.perf_counter(), bench=b, base=base,
                            check_device=False, control=control)


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.doc"])
def test_sound_run_is_correct(bench, cell):
    res = _run(bench, cell)
    line = res.line
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 10
    want = {m.name for m in bench[0].end_to_end_of(cell)}
    assert set(line["metrics"]) == want
    assert list(line)[-1] == "checks"
    assert line["run"]["compiled_in_window"]["programs"] == []
    assert line["run"]["compiled_in_window"]["step_traces"] == 0
    assert line["checks"]["widest_logit_gap"]["value"] <= 0.005


def test_token_altered_where_produced_is_not_correct(bench, monkeypatch):
    real = Request.emit_many

    def altered(self, toks):
        toks = list(toks)
        toks[-1] = (toks[-1] + 1) % 256
        return real(self, toks)

    monkeypatch.setattr(Request, "emit_many", altered)
    res = _run(bench, "tiny.chat")
    assert not res.line["correct"]
    assert res.line["checks"]["widest_logit_gap"]["value"] > 0.005


def test_answer_cut_short_is_not_correct(bench, monkeypatch):
    real = Request.emit_many

    def dropped(self, toks):
        return real(self, list(toks)[:-1] or list(toks))

    monkeypatch.setattr(Request, "emit_many", dropped)
    res = _run(bench, "tiny.chat")
    assert not res.line["correct"]
    assert res.line["checks"]["unfinished_requests"]["value"] > 0


def test_control_in_the_programs_place_is_not_correct(bench):
    res = _run(bench, "tiny.chat", control=True)
    assert res.line["correct"], res.line["checks"]
    ctrl = res.verdict.control
    assert not ctrl.correct
    gap = ctrl.checks["widest_logit_gap"]
    assert gap["value"] > gap["limit"] == 0.005
    assert res.line["run"]["control"]["correct"] is False
