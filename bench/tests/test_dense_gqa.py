"""Pins of the dense GQA family's numbers, taken from the code before the
harness reached it through ``bench/families``: counts at the published
shapes, weights and reference logits at tiny shapes."""
import hashlib

import jax
import numpy as np
import pytest

from bench.dims import load_config
from bench.families import dense_gqa, family_of
from bench.tests.tiny import tiny_config
from bench.weights import make_weights

NAMES = ["mistral-nemo-12b", "qwen3-32b"]
KEYS = [274, 1948, 6144]
COUNTS = {   # decode bytes, decode FLOPs, prefill FLOPs (0, 6144), (1500, 512)
    "mistral-nemo-12b": (5_978_693_632, 18_209_308_672,
                         29_274_899_742_720, 2_351_259_713_536),
    "qwen3-32b": (7_612_722_176, 23_865_458_688,
                  39_660_331_991_040, 3_172_554_768_384),
}
SEED = 3141500011
WEIGHTS_SHA256 = {"mistral-nemo-12b": "609ad9e17c2b084e",
                  "qwen3-32b": "79103b61157a07d3"}
LOGITS = {"mistral-nemo-12b": (0.41905755, 236),   # max, argmax at row 39
          "qwen3-32b": (0.32631442, 135)}


@pytest.mark.parametrize("name", NAMES)
def test_configuration_files_name_dense_gqa(name):
    assert family_of(load_config(name)) is dense_gqa


@pytest.mark.parametrize("name", NAMES)
def test_counts(name):
    d = dense_gqa.dims_of(load_config(name))
    meta = {"rows": 3, "keys": sum(KEYS)}
    assert (dense_gqa.decode_bytes(d, KEYS, meta),
            dense_gqa.decode_flops(d, KEYS, meta),
            dense_gqa.prefill_flops(d, 0, 6144, {"n": 6144}),
            dense_gqa.prefill_flops(d, 1500, 512, {"n": 512})) \
        == COUNTS[name]


@pytest.mark.parametrize("name", NAMES)
def test_weights_and_reference_logits(name):
    d = dense_gqa.dims_of(tiny_config(name))
    w = make_weights(dense_gqa, d, SEED)
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(w):
        h.update(np.asarray(leaf, np.float32).tobytes())
    assert h.hexdigest()[:16] == WEIGHTS_SHA256[name]
    logits = dense_gqa.logits_at(w, d, list(range(1, 41)), [39])
    top, arg = LOGITS[name]
    assert float(logits.max()) == pytest.approx(top, abs=1e-6)
    assert int(logits.argmax()) == arg
