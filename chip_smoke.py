#!/usr/bin/env python3
"""Chip smoke: serve llama3.2-3b at full width and depth on a TPU through
the user entry points (``SagaClient.for_runtime`` -> ``ServingRuntime`` ->
paged ``Engine`` prefill and decode), with random weights from a seed.

    python chip_smoke.py             # one chip: phases "serve" and "check"
    python chip_smoke.py --chips 4   # four chips: phase "placement" only

Phases:

  serve      six agent sessions from two tenants over two engines: a
             1024-token first prompt, then three tool steps of 96-160 new
             tokens, 32 decoded tokens per step, virtual tool gaps of
             0.5-2 s.  Exercises park, resume with delta prefill and
             cross-engine KV copies; every session must finish with
             ``check_conservation()`` and every ``audit_blocks()`` clean.
  check      one session through paged prefill and 8 paged decode steps;
             each step's logits must match a full-sequence
             ``lm.forward_logits`` over the same tokens.
  placement  one engine per chip against the same run with every engine
             on chip 0: byte-identical ``summarize()``, identical step
             outputs, and KV copied between chips.

Each phase prints one ``<phase>: {json}`` line; the last line of standard
output is ``{"ok": true, "device": {...}}``.  The script refuses to run
anywhere but a TPU, runs in one process, and keeps its compile cache where
``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import numpy as np

from repro.configs import get_config, load_all
from repro.core.coordinator import SAGAConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.client import SagaClient
from repro.serving.engine import Engine
from repro.serving.runtime import AgentRequest, ServingRuntime

ARCH = "llama3.2-3b"
SEED = 0
TRAFFIC_SEED = 3
TOOLS = ("code_execution", "web_api", "file_operations")
# one-chip serving sizes: 2 engines x (512 nominal + 4 slots x 128 headroom
# blocks) x 1.75 MiB per 16-token block of llama3.2-3b KV = 1.75 GiB of pool
# per engine next to 6 GiB of bf16 params
SERVE = dict(n_workers=2, n_slots=4, max_len=2048, pool_blocks=512)
# four-chip placement arms: smaller pools so that four engines AND the
# params replica fit on chip 0 in the all-on-one-chip arm
PLACE = dict(n_workers=4, n_slots=2, max_len=2048, pool_blocks=128)
# an engine counts as loaded (Eq. 7 diverts, speculative prefetch
# replicates) at half its slots, so six sessions on two engines overflow
# and parked KV is copied between engines
THETA = 0.5
CHECK_PROMPT, CHECK_STEPS = 1016, 8
# paged decode (gathered block view, one token) and the full-sequence
# forward (causal attention over the whole prompt) fuse and order their
# bf16 matmuls and f32 softmax reductions differently; the residual stream
# is rounded to bf16 after every sublayer, so a one-ulp difference can
# flip and grow with depth.  The error is ||decode - ref|| / ||ref|| per
# step.  On the CPU at llama3.2-3b widths it measured 0.011 at 2 layers
# and 0.025 at 8; the same check with the decode mask dropping the newest
# token measured 0.10 and 0.14, with RoPE one position late 0.42 and 0.67,
# and a wrong block table reads unrelated KV.
CHECK_REL_TOL = 8e-2


class CompileCounter:
    """Counts backend compiles (cache misses) and their seconds."""

    def __init__(self) -> None:
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def agent_sessions(vocab: int, *, n_sessions: int = 6, n_tenants: int = 2,
                   first_prompt: int = 1024, n_tool_steps: int = 3,
                   n_decode: int = 32, arrival_gap_s: float = 1.0 / 6,
                   seed: int = TRAFFIC_SEED):
    """Agent traffic: a long first prompt, then tool steps whose new
    tokens come in three sizes (three delta-prefill compile buckets).
    Staggered arrivals keep the sessions from parking in lockstep."""
    rng = np.random.RandomState(seed)

    def ids(n):
        return [int(t) for t in rng.randint(1, vocab, size=n)]

    reqs = []
    for i in range(n_sessions):
        steps = [(ids(first_prompt), n_decode, TOOLS[0],
                  float(rng.uniform(0.5, 2.0)))]
        for s in range(n_tool_steps):
            steps.append((ids(int(rng.choice([96, 128, 160]))), n_decode,
                          TOOLS[(s + 1) % len(TOOLS)],
                          float(rng.uniform(0.5, 2.0))))
        reqs.append(AgentRequest(f"agent-{i}", f"tenant-{i % n_tenants}",
                                 steps, arrival_s=i * arrival_gap_s))
    return reqs


def _drive(rt: ServingRuntime, reqs) -> dict:
    """Submit through the client, run to completion, and hold the run to
    its lifecycle invariants."""
    client = SagaClient.for_runtime(rt)
    handles = [client.submit(r) for r in reqs]
    client.run()
    unfinished = [h.session_id for h in handles if not h.done]
    if unfinished:
        raise RuntimeError(f"sessions did not finish: {unfinished}")
    client.check_conservation()
    for w, eng in enumerate(rt.engines):
        errs = eng.pool.audit_blocks()
        if errs:
            raise RuntimeError(f"engine {w} block audit: {errs[:3]}")
    return {h.session_id: h.step_outputs for h in handles}


def serve_phase(cfg, params, reqs, *, n_workers: int, n_slots: int,
                max_len: int, pool_blocks: int):
    """Returns (runtime, report) after serving ``reqs`` to completion."""
    rt = ServingRuntime(cfg, params, n_workers=n_workers,
                        saga=SAGAConfig(theta=THETA), n_slots=n_slots,
                        max_len=max_len, pool_blocks=pool_blocks, seed=SEED)
    t0 = time.perf_counter()
    _drive(rt, reqs)
    st, sm = rt.stats(), rt.summarize()
    if st["migration_copy_bytes"] <= 0:
        raise RuntimeError("no cross-engine KV copy: the traffic did not "
                           "exercise steal / prefetch")
    if sm["cache_hits"] <= 0:
        raise RuntimeError("no resume hit: the traffic did not exercise "
                           "park and delta prefill")
    return rt, {
        "sessions_finished": sm["n_done"], "sessions": sm["n_sessions"],
        "prefill_tokens": st["prefill_tokens"],
        "regen_tokens": st["regen_tokens"],
        "decoded_tokens": sm["decoded_tokens"],
        "decode_rounds": st["decode_steps"],
        "cache_hits": sm["cache_hits"], "steals": sm["steals"],
        "prefetch_copies": sm["prefetch_copies"],
        "cross_engine_copy_bytes": st["migration_copy_bytes"],
        "conservation": "clean", "audit_blocks": "clean",
        "wall_s": time.perf_counter() - t0,
    }


def check_phase(eng: Engine, *, prompt_len: int = CHECK_PROMPT,
                n_steps: int = CHECK_STEPS, seed: int = SEED) -> dict:
    """Paged prefill + ``n_steps`` paged decode steps on ``eng`` against a
    full-sequence forward over the same tokens.  Decode step i feeds token
    f_i at position prompt_len + i (f_0 is the prompt's last token, as the
    runtime feeds it), so its logits are the reference's at that row."""
    cfg = eng.cfg
    rng = np.random.RandomState(seed + 1)
    prompt = rng.randint(1, cfg.vocab, size=prompt_len).astype(np.int32)
    slot = eng.start_session("check", prompt, cached_hit=False)
    fed, got = [int(prompt[-1])], []
    for _ in range(n_steps):
        row = eng.paged_step_logits({slot: fed[-1]})[slot, 0]
        got.append(np.asarray(row, np.float32))
        fed.append(int(np.argmax(got[-1])))
    eng.release_session("check")
    seq = np.concatenate([prompt, np.asarray(fed[:-1], np.int32)])

    @jax.jit
    def reference(params, tokens):
        logits = lm.forward_logits(params, {"tokens": tokens}, cfg, eng.env)
        return logits[0, prompt_len:]

    ref = np.asarray(reference(eng.params,
                               jax.device_put(seq[None], eng.device)),
                     np.float32)
    got = np.stack(got)

    def rel_err(a, b):
        """Largest per-step ||a - b|| / ||b|| over the vocab."""
        return float(np.max(np.linalg.norm(a - b, axis=-1)
                            / np.linalg.norm(b, axis=-1)))

    err = rel_err(got, ref)
    out = {"steps": n_steps, "prompt_tokens": prompt_len,
           "max_rel_logit_err": err, "tolerance": CHECK_REL_TOL,
           # the same comparison one position off must fail the tolerance
           "off_by_one_rel_err": rel_err(got[1:], ref[:-1]),
           "max_abs_logit_err": float(np.max(np.abs(got - ref))),
           "argmax_agree": int(np.sum(got.argmax(-1) == ref.argmax(-1)))}
    if not np.all(np.isfinite(got)):
        raise RuntimeError(f"non-finite decode logits: {out}")
    if not err <= CHECK_REL_TOL < out["off_by_one_rel_err"]:
        raise RuntimeError(f"paged decode disagrees with forward: {out}")
    return out


def placement_phase(cfg, params, reqs, *, n_workers: int, n_slots: int,
                    max_len: int, pool_blocks: int) -> dict:
    """One engine per device (the runtime's own placement) against every
    engine on device 0, same traffic and seed."""
    devs = jax.devices()
    if len(devs) < n_workers:
        raise RuntimeError(f"placement needs {n_workers} devices, "
                           f"found {len(devs)}")
    sizes = dict(n_slots=n_slots, max_len=max_len, pool_blocks=pool_blocks)
    spread = ServingRuntime(cfg, params, n_workers=n_workers,
                            saga=SAGAConfig(theta=THETA), seed=SEED, **sizes)
    homes = sorted({e.device.id for e in spread.engines})
    if len(homes) != n_workers:
        raise RuntimeError(f"engines share devices: {homes}")
    out_a = _drive(spread, reqs)
    sum_a, st_a = repr(spread.summarize()), spread.stats()
    del spread
    gc.collect()
    one = ServingRuntime(cfg, params, saga=SAGAConfig(theta=THETA),
                         seed=SEED,
                         engines=[Engine(cfg, params, device=devs[0],
                                         **sizes)
                                  for _ in range(n_workers)])
    out_b = _drive(one, reqs)
    sum_b = repr(one.summarize())
    if sum_a != sum_b:
        raise RuntimeError(f"placement changed the summary:\n  spread "
                           f"{sum_a}\n  one-device {sum_b}")
    if out_a != out_b:
        raise RuntimeError("placement changed the decoded tokens")
    # every engine of the spread arm owns its own device, so every
    # inter-engine KV copy crossed devices
    if st_a["migration_copy_bytes"] <= 0:
        raise RuntimeError("no KV crossed devices in the spread arm")
    return {"devices": homes, "summary_identical": True,
            "outputs_identical": True,
            "cross_device_copy_bytes": st_a["migration_copy_bytes"],
            "steals": st_a["steals"],
            "prefetch_copies": st_a["prefetch_copies"],
            "summary": sum_a}


def _memory(dev) -> dict:
    """HBM in use now, its peak so far, and the allocator's limit."""
    stats = dev.memory_stats() or {}
    return {k: stats.get(k, "not reported")
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _emit(name: str, payload: dict) -> None:
    print(f"{name}: {json.dumps(payload)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the one-engine-per-chip placement "
                         "phase")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    compiles = CompileCounter()
    load_all()
    cfg = get_config(ARCH)
    _emit("config", {"arch": cfg.name, "n_layers": cfg.n_layers,
                     "d_model": cfg.d_model, "n_heads": cfg.n_heads,
                     "n_kv_heads": cfg.n_kv_heads, "d_ff": cfg.d_ff,
                     "vocab": cfg.vocab, "seed": SEED,
                     "compile_cache": cache_dir,
                     "device_kind": devs[0].device_kind})
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        lm.init_params(cfg, jax.random.PRNGKey(SEED)))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    _emit("init", {"params": n_params, "wall_s": time.perf_counter() - t0,
                   "memory": _memory(devs[0])})
    reqs = agent_sessions(cfg.vocab)
    if args.chips == 4:
        _emit("placement", placement_phase(cfg, params, reqs, **PLACE))
    else:
        rt, rep = serve_phase(cfg, params, reqs, **SERVE)
        _emit("serve", dict(rep, memory=_memory(devs[0])))
        _emit("check", check_phase(rt.engines[0]))
        errs = rt.engines[0].pool.audit_blocks()
        if errs:
            raise RuntimeError(f"check phase left the pool dirty: {errs}")
    _emit("device", {"compiles": compiles.n,
                     "compile_s": compiles.seconds,
                     "memory": [_memory(d) for d in devs[:args.chips]]})
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
