"""The control at tiny size: the reference computed in float8 in the
program's place reads above the limit that sound runs read under."""
import pytest

from bench.control import readings
from bench.tests.tiny import tiny_config, tiny_mix

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(autouse=True)
def _private_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("name", ["mistral-nemo-12b", "qwen3-32b"])
def test_control_fails_the_limit(name):
    spec = tiny_config(name)
    limit = spec["check"]["max_logit_gap"]
    r = readings(spec, tiny_mix(), 2**31 + 5, 2.0, 1, lambda m: None,
                 device_peaks=PEAKS)
    assert r["tokens"] >= 40
    assert r["served"] <= limit < r["control"], r
