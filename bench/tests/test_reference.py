"""The plain reference against the program's full-sequence forward at
tiny sizes of both configurations (the program in bf16, the reference in
float32, on the same weights)."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.dims import dims_of
from bench.harness import model_config
from bench.tests.tiny import tiny_config
from bench.weights import make_weights

NAMES = ["mistral-nemo-12b", "qwen3-32b"]


def _program_logits(spec, params, tokens):
    from repro.models import lm
    from repro.serving.engine import serving_env
    cfg = model_config(spec, dims_of(spec))
    out = lm.forward_logits(params, {"tokens": jnp.asarray([tokens])}, cfg,
                            serving_env())
    return np.asarray(out[0], np.float32)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("name", NAMES)
def test_reference_matches_program_forward(name):
    spec = tiny_config(name)
    d = dims_of(spec)
    params = make_weights(d, 11)
    tokens = list(np.random.default_rng(0).integers(1, d.vocab, 40))
    rows = list(range(len(tokens)))
    ref = reference.logits_at(params, d, tokens, rows)
    got = _program_logits(spec, params, tokens)
    # bf16 activations against float32: about 1% of the logits' norm
    assert _rel(got, ref) < 0.03
    # the same comparison one position off is far outside that
    assert _rel(got[1:], ref[:-1]) > 0.3


@pytest.mark.parametrize("name", NAMES)
def test_padding_leaves_rows_unchanged(name):
    d = dims_of(tiny_config(name))
    params = make_weights(d, 3)
    tokens = list(np.random.default_rng(1).integers(1, d.vocab, 600))
    a = reference.logits_at(params, d, tokens[:300], [10, 299])
    b = reference.logits_at(params, d, tokens, [10, 299])
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_fp8_control_departs_from_reference():
    d = dims_of(tiny_config("mistral-nemo-12b"))
    params = make_weights(d, 5)
    tokens = list(np.random.default_rng(2).integers(1, d.vocab, 64))
    rows = list(range(64))
    ref = reference.logits_at(params, d, tokens, rows)
    low = reference.logits_at(params, d, tokens, rows, precision="fp8")
    assert 0.02 < _rel(low, ref) < 0.5
