"""The configuration files hold the published widths; only depth is cut."""
import json
from pathlib import Path

import pytest

from bench.dims import dims_of, load_config
from bench.weights import n_params

ROOT = Path(__file__).resolve().parents[2]

PUBLISHED = {
    "mistral-nemo-12b": dict(hidden_size=5120, num_attention_heads=32,
                             num_key_value_heads=8, head_dim=128,
                             intermediate_size=14336, vocab_size=131072,
                             rope_theta=1e6, rms_norm_eps=1e-5,
                             tie_word_embeddings=False),
    "qwen3-32b": dict(hidden_size=5120, num_attention_heads=64,
                      num_key_value_heads=8, head_dim=128,
                      intermediate_size=25600, vocab_size=151936,
                      rope_theta=1e6, rms_norm_eps=1e-6,
                      tie_word_embeddings=False),
}
DEPTH = {"mistral-nemo-12b": (40, 8), "qwen3-32b": (64, 6)}
# Nemo: 8 x (5120*128*(2*32+2*8) + 3*5120*14336 + 2*5120)
#       + 2 * 131072*5120 + 5120
# Qwen3: 6 x (5120*128*(2*64+2*8) + 3*5120*25600 + 2*5120 + 2*128)
#       + 2 * 151936*5120 + 5120
PARAMS = {"mistral-nemo-12b": 8 * 272_640_000 + 1_342_177_280 + 5120,
          "qwen3-32b": 6 * 487_598_336 + 1_555_824_640 + 5120}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_widths(name):
    spec = load_config(name)
    for k, v in PUBLISHED[name].items():
        assert spec[k] == v, k
    assert spec["published"]["num_hidden_layers"] == DEPTH[name][0]
    assert spec["num_hidden_layers"] == DEPTH[name][1]
    assert spec["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_parameter_count(name):
    assert n_params(dims_of(load_config(name))) == PARAMS[name]


def test_benchmark_names_the_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert load_config(c["name"])["source"] == c["source"]
        assert load_config(c["name"])["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_qk_norm_as_the_files_state():
    assert dims_of(load_config("qwen3-32b")).qk_norm
    assert not dims_of(load_config("mistral-nemo-12b")).qk_norm
