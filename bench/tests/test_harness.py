"""A whole run at tiny size on the CPU: the harness's look for a chip is
skipped (``serve_and_check`` is called directly), everything else runs,
and a fault planted in the served path turns ``correct`` false."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.harness import serve_and_check
from bench.tests.tiny import tiny_config, tiny_mix

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
E2E = [{"name": n, "unit": "u"} for n in
       ("ttft_p90_ms", "tpot_p90_ms", "setup_s")]
SEED = 2**31 + 99


@pytest.fixture(autouse=True)
def _private_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(name="mistral-nemo-12b", seconds=2.0):
    return serve_and_check(tiny_config(name), tiny_mix(), seed=SEED,
                           seconds=seconds, trace=False, chips=1,
                           metrics=E2E, t_process=time.perf_counter(),
                           device_peaks=PEAKS)


@pytest.mark.parametrize("name", ["mistral-nemo-12b", "qwen3-32b"])
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["check"]
    assert list(out)[0] == "correct" and list(out)[-1] == "check"
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared_tokens"] >= 40
    assert set(out["metrics"]) == {m["name"] for m in E2E}
    assert out["metrics"]["ttft_p90_ms"]["value"] > 0


def test_altered_token_is_caught(monkeypatch):
    """A token altered where the engine produces it."""
    from repro.serving import engine

    real = engine.Engine._decode_paged
    calls = {"n": 0}

    def altered(self, slot_tokens, n_steps):
        out = real(self, slot_tokens, n_steps)
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            s = min(out)
            out[s] = [(t + 1) % self.cfg.vocab for t in out[s]]
        return out

    monkeypatch.setattr(engine.Engine, "_decode_paged", altered)
    out = _run()
    assert not out["correct"], out["check"]


def test_decode_that_leaves_the_pool_unchanged_is_caught(monkeypatch):
    """A decode step that returns its state (the K/V pool) unchanged.
    The round donates the pool, so the state put back is a copy taken
    before it (the pool itself is deleted by then)."""
    import jax.numpy as jnp
    from repro.serving import engine

    real = engine.Engine.paged_step_logits

    def stale(self, slot_tokens):
        k, v = jnp.copy(self.pool.k_pool), jnp.copy(self.pool.v_pool)
        logits = real(self, slot_tokens)
        self.pool.k_pool, self.pool.v_pool = k, v
        return logits

    monkeypatch.setattr(engine.Engine, "paged_step_logits", stale)
    assert not _run()["correct"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    """Decode rows in the upper half of the batch take the lower half's
    tokens instead of their own."""
    from repro.serving import engine

    real = engine.Engine._decode_paged

    def half(self, slot_tokens, n_steps):
        out = real(self, slot_tokens, n_steps)
        rows = sorted(out)
        keep = rows[:max(1, len(rows) // 2)]
        for i, s in enumerate(rows[len(keep):]):
            out[s] = list(out[keep[i % len(keep)]])
        return out

    monkeypatch.setattr(engine.Engine, "_decode_paged", half)
    assert not _run()["correct"]


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "-m", "bench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_the_cpu():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    r = _bench(["--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0"], ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    r = _bench(["--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0"], tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
