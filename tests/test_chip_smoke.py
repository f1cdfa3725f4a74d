"""chip_smoke.py rehearsed on the CPU: its serve and check phases on the
micro model, its refusal to run anywhere but a TPU, and one engine per
device on four virtual CPU devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, load_all
from repro.models import lm

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def micro():
    load_all()
    cfg = get_config("micro")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(chip_smoke.SEED))


def test_serve_and_check_phases_on_micro(micro):
    cfg, params = micro
    rt, rep = chip_smoke.serve_phase(cfg, params,
                                     chip_smoke.agent_sessions(cfg.vocab),
                                     **chip_smoke.SERVE)
    assert rep["sessions_finished"] == rep["sessions"] == 6
    assert rep["cross_engine_copy_bytes"] > 0 and rep["cache_hits"] > 0
    assert rep["decoded_tokens"] == 6 * 4 * 32
    chk = chip_smoke.check_phase(rt.engines[0])
    assert chk["max_rel_logit_err"] <= chk["tolerance"] \
        < chk["off_by_one_rel_err"]
    assert rt.engines[0].pool.audit_blocks() == []
    assert not rt.engines[0].pool.tables


def test_main_refuses_the_cpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_env(JAX_PLATFORMS="cpu"), capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


_PLACEMENT = """
import json, sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke as cs
from repro.configs import get_config, load_all
from repro.models import lm
from repro.serving.runtime import ServingRuntime
load_all()
cfg = get_config("micro")
params = lm.init_params(cfg, jax.random.PRNGKey(cs.SEED))
rt = ServingRuntime(cfg, params, n_workers=2, max_len=64, pool_blocks=8)
rt._scale_up()
homes = [e.device.id for e in rt.engines]
out = cs.placement_phase(cfg, params, cs.agent_sessions(cfg.vocab),
                         **cs.PLACE)
print(json.dumps(dict(out, homes=homes)))
"""


def test_one_engine_per_device_on_four_cpu_devices():
    """Engines spread one per device (scale-up included) serve the same
    summary and tokens as every engine on device 0, with KV copied across
    devices."""
    r = subprocess.run(
        [sys.executable, "-c", _PLACEMENT.format(root=str(ROOT))],
        env=_env(JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["homes"] == [0, 1, 2]
    assert out["devices"] == [0, 1, 2, 3]
    assert out["summary_identical"] and out["outputs_identical"]
    assert out["cross_device_copy_bytes"] > 0
