"""The plain f32 reference against the model, and the served path against
the reference — the check ``chip_smoke.py`` makes on the chip, here at a
tiny size on the CPU through the same build path."""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.launch.serve import build_engine, quantize_for_serving
from repro.models.reference import greedy_gaps, reference_logits
from repro.serve import Request


@pytest.fixture(scope="module")
def quantized():
    return quantize_for_serving(ARCHS["llama3-8b"].tiny(), calib_n=8)


@pytest.mark.parametrize("packed", [False, True])
def test_reference_matches_model_forward(quantized, packed):
    """Same weights, same tokens: the reference's logits equal the model's
    full forward (f32 tiny config) up to f32 summation order."""
    model = quantized.model
    params = (quantized.qparams if packed
              else model.init(jax.random.PRNGKey(0)))
    tokens = quantized.data.sequence(123, 40)
    ref = np.asarray(reference_logits(model.cfg, params, tokens))
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": tokens[None]})
    got = np.asarray(got[0, :, :model.cfg.vocab_size])
    assert ref.shape == got.shape
    np.testing.assert_allclose(ref, got, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("spec_k", [0, 4])
def test_served_tokens_are_reference_argmaxes(quantized, spec_k):
    """Paged serving (with chunked prefill, and with the self-int8 draft
    at spec_k=4) emits, at every position, a token whose reference logit
    is the reference maximum up to f32 noise."""
    eng = build_engine(quantized, spec_k=spec_k, n_slots=2, max_len=128,
                       paged=True)
    prompts = {i: quantized.data.sequence(40_000_000 + i, n)
               for i, n in enumerate((70, 9, 33))}
    out = eng.serve([Request(rid=i, prompt=p, max_new_tokens=10)
                     for i, p in prompts.items()])
    assert eng.metrics()["chunked_admissions"] >= 1
    for rid, prompt in prompts.items():
        seq = np.concatenate([prompt, out[rid]])
        gaps = greedy_gaps(reference_logits(quantized.model.cfg,
                                            quantized.qparams, seq),
                           len(prompt), out[rid])
        assert len(gaps) == 10 and gaps.max() < 1e-3, gaps


def test_greedy_gaps_indexing():
    logits = np.zeros((5, 4), np.float32)
    logits[2, 1] = 1.0     # position 2 predicts token 1 (reference argmax)
    logits[3, 3] = 2.0     # position 3's argmax is 3; the engine emits 0
    np.testing.assert_array_equal(greedy_gaps(logits, 3, [1, 0]), [0.0, 2.0])
