"""The dense family's plain float32 reference against the program at a
small size on the CPU: the program's own float32 forward, and greedy
tokens served by the engine's bucketed prefill and its decode through
the paged cache (gather path and page-table path).  The fp8 control
must read wider gaps than the served tokens do."""
import correctness
import jax
import numpy as np
import pytest
import reference
import spec
import weights as weights_lib
from loadgen import Record
from traffic import RequestSpec

from repro.models import build
from repro.serving import Request, SamplingParams
from repro.serving.engine import EngineConfig, InferenceEngine

ARCH = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 256, "norm": "rms",
        "act": "swiglu", "tie_embeddings": True, "rope_theta": 10000.0,
        "dtype": "bf16"}
OLMO_LIKE = dict(ARCH, n_kv_heads=4, norm="nonparam_ln")
# served bf16 tokens at this size read gaps of about 1e-4 to 1e-3; the
# fp8 control about 1e-2 and more (see test_control_reads_wider_gaps)
LIMIT = 0.005
DENSE = spec.load_family("dense")


def _cfg(arch):
    return DENSE.build_arch({"name": "tiny", "arch": arch})


def _weights(arch, seed):
    cfg = _cfg(arch)
    shapes = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    return weights_lib.make(shapes, seed, jax.devices()[0], DENSE.leaf_std)


@pytest.mark.parametrize("arch", [ARCH, OLMO_LIKE], ids=["rms-gqa", "ln-mha"])
def test_reference_matches_the_programs_float32_forward(arch):
    arch = dict(arch, dtype="f32")
    w = _weights(arch, 11)
    toks = np.random.default_rng(0).integers(1, 256, size=37)
    got = np.asarray(build(_cfg(arch)).forward(w, toks[None])[0][0])
    want = DENSE.logits(w, arch, toks, pad_to=64)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def _serve(arch, w, prompts, max_tokens, paged_attention):
    eng = InferenceEngine(_cfg(arch), w, EngineConfig(
        n_slots=4, max_len=64, paged_attention=paged_attention))
    reqs = [Request(model="tiny", prompt=list(p),
                    sampling=SamplingParams(max_tokens=max_tokens))
            for p in prompts]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_done()
    return [list(r.output) for r in reqs]


@pytest.mark.parametrize("paged_attention", [False, True],
                         ids=["gather", "page-table"])
def test_served_tokens_agree_with_the_reference(paged_attention):
    w = _weights(ARCH, 12)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 17, 30)]
    outs = _serve(ARCH, w, prompts, 20, paged_attention)
    for p, o in zip(prompts, outs):
        assert len(o) == 20
        g, _ = reference.gaps(DENSE, w, ARCH, p, o, pad_to=64)
        assert g.max() <= LIMIT


def test_control_reads_wider_gaps_and_a_wrong_token_fails():
    w = _weights(ARCH, 13)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (9, 25)]
    outs = _serve(ARCH, w, prompts, 24, False)
    widest, control = 0.0, 0.0
    for p, o in zip(prompts, outs):
        g, c = reference.gaps(DENSE, w, ARCH, p, o, pad_to=64,
                              control=True)
        widest, control = max(widest, g.max()), max(control, c.max())
        bad = list(o)
        bad[5] = (bad[5] + 1) % 256
        gb, _ = reference.gaps(DENSE, w, ARCH, p, bad, pad_to=64)
        assert gb.max() > LIMIT
    assert widest <= LIMIT < control


def test_control_decoded_in_the_programs_place_fails_the_judge():
    """The fp8 control serves greedy answers in the program's place;
    `correctness.judge` at the cell's limit finds them not correct."""
    w = _weights(ARCH, 14)
    rng = np.random.default_rng(3)
    records = []
    for i, n in enumerate((8, 21)):
        prompt = rng.integers(1, 256, size=n).tolist()
        out = []
        for _ in range(20):
            lg = DENSE.logits(w, ARCH, prompt + out, pad_to=64,
                              precision="fp8")
            out.append(int(np.argmax(lg[-1])))
        spec = RequestSpec(i, "window", 0.0, np.asarray(prompt, np.int32),
                           len(out))
        records.append(Record(spec, 0.0, tokens=out, node="n0",
                              finished=True))
    cfg = {"limits": {"widest_logit_gap": LIMIT}, "engine": {"max_len": 64}}
    verdict = correctness.judge(records, records, DENSE, w, ARCH, cfg)
    assert not verdict.correct
    assert verdict.checks["widest_logit_gap"]["value"] > LIMIT
    assert verdict.checks["unfinished_requests"]["value"] == 0
