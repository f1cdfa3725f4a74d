"""The harness reaches the architecture only through the configuration's
family, and hands the engine's span meta to the metric readers in
traced runs."""
import json
import time
from pathlib import Path

import pytest

from bench import families, harness
from bench.families import dense_gqa, family_of
from bench.harness import (PrefillRec, RoundRec, RunRecord, Server,
                           load_reader, serve_and_check)
from bench.tests.tiny import tiny_config, tiny_mix
from bench.trace_reduce import TraceSummary
from bench.traffic import Request

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 1501


@pytest.fixture(autouse=True)
def _private_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.fixture
def recording(monkeypatch):
    """The test-only family ``bench/tests/families/recording.py``, found
    by the loader as a configuration's family would be."""
    monkeypatch.setattr(families, "PACKAGE", "bench.tests.families")
    fam = family_of({"name": "t", "family": "recording"})
    fam.CALLS.clear()
    return fam


def test_serving_and_check_go_through_the_family(recording):
    out = serve_and_check(tiny_config("mistral-nemo-12b", family="recording"),
                          tiny_mix(), seed=SEED, seconds=2.0, trace=False,
                          chips=1, metrics=[], t_process=time.perf_counter(),
                          device_peaks=PEAKS)
    assert out["correct"], out["check"]
    called = {name for name, _ in recording.CALLS}
    assert {"dims_of", "program_config", "layout", "logits_at"} <= called


def _synthetic_run(fam):
    d = fam.dims_of(tiny_config("qwen3-32b"))
    trace = TraceSummary(window_s=2.0, busy_s=1.0,
                         module_s={"jit_paged_decode_fn(7)": 0.25,
                                   "jit_prefill_fn": 0.5},
                         gaps=[], window_ns=(0.0, 2e9), n_devices=1)
    rounds = [RoundRec(1.0, 1.1, [40, 90], {"rows": 2, "keys": 130}),
              RoundRec(1.2, 1.3, [41, 91], {"rows": 2, "keys": 132}),
              RoundRec(3.5, 3.6, [42, 92], {"rows": 2, "keys": 134})]
    prefills = [PrefillRec(0.9, 0.95, 39, {"n": 39, "pad_to": 64}),
                PrefillRec(3.0, 3.05, 89, {"n": 89, "pad_to": 96})]
    return RunRecord(family=fam, dims=d, peaks=PEAKS, t_process=0.0,
                     t0=0.5, t1=4.0, requests=[], rounds=rounds,
                     prefills=prefills, compiles_window=0, trace=trace,
                     trace_pc=(0.8, 2.8))


def test_roofline_readers_call_the_familys_counts(recording):
    run = _synthetic_run(recording)
    traced = run.rounds[:2]
    d = run.dims

    recording.CALLS.clear()
    need = sum(max(dense_gqa.decode_bytes(d, r.keys, {}) / 1e11,
                   dense_gqa.decode_flops(d, r.keys, {}) / 1e12) for r in traced)
    assert load_reader("decode_roofline")(run) == pytest.approx(
        100 * need / 0.25)
    want = [(n, r) for r in traced for n in ("decode_bytes", "decode_flops")]
    assert [n for n, _ in recording.CALLS] == [n for n, _ in want]
    for (_, args), (_, r) in zip(recording.CALLS, want):
        assert args[0] is d and args[1] == r.keys and args[2] is r.meta

    recording.CALLS.clear()
    flops = sum(dense_gqa.decode_flops(d, r.keys, {}) for r in traced) \
        + dense_gqa.prefill_flops(d, 0, 39, {})
    assert load_reader("step_mfu")(run) == pytest.approx(
        100 * flops / (2.0 * 1e12))
    want = [r.meta for r in traced] + [run.prefills[0].meta]
    assert [n for n, _ in recording.CALLS] == \
        ["decode_flops"] * 2 + ["prefill_flops"]
    assert all(args[-1] is meta
               for (_, args), meta in zip(recording.CALLS, want))


class _SpanEngine:
    """An engine stand-in that records ``engine.prefill`` and
    ``engine.decode`` spans, each with a child, as the engine does."""

    n_slots = 2

    def __init__(self):
        from repro.obs.tracer import Tracer
        self.tracer = Tracer(clock=time.perf_counter)
        self.slots = {}              # slot -> [session id, cached tokens]

    def free_slot(self):
        return next((s for s in range(self.n_slots) if s not in self.slots),
                    None)

    def start_session(self, sid, tokens, cached_hit):
        slot = self.free_slot()
        with self.tracer.span("engine", "engine.prefill", sid=sid,
                              n=len(tokens)) as top:
            with self.tracer.span("engine", "engine.prefill.prep",
                                  parent=top, n=-1):
                pass
        self.slots[slot] = [sid, len(tokens)]
        return slot

    def decode(self, slot_tokens, n_steps=1):
        lens = [self.slots[s][1] + 1 for s in slot_tokens]
        with self.tracer.span("engine", "engine.decode", rows=len(lens),
                              keys=sum(lens)) as top:
            with self.tracer.span("engine", "engine.decode.prep",
                                  parent=top, rows=-1):
                pass
        for s in slot_tokens:
            self.slots[s][1] += 1
        return {s: [3] for s in slot_tokens}

    def release_session(self, sid):
        del self.slots[next(s for s, v in self.slots.items() if v[0] == sid)]


def test_span_meta_goes_to_its_own_call():
    reqs = [Request(f"r{i}", 0.02 * i, [1] * (5 + 7 * i), 2 + i % 3)
            for i in range(12)]
    srv = Server(_SpanEngine(), reqs, warm_s=0.05, seconds=0.2,
                 trace_dir=None)
    srv.run()
    assert len(srv.prefills) == 12 and srv.rounds
    assert [p.meta["n"] for p in srv.prefills] == [p.n for p in srv.prefills]
    assert [p.meta["sid"] for p in srv.prefills] == [r.rid for r in reqs]
    assert [(r.meta["rows"], r.meta["keys"]) for r in srv.rounds] == [
        (len(r.keys), sum(r.keys)) for r in srv.rounds]


@pytest.mark.parametrize("trace", [False, True])
def test_engine_spans_reach_the_run_record(monkeypatch, tmp_path, trace):
    """A whole tiny run: the engine's spans and their meta reach the
    readers' ``RunRecord`` in a traced run, and nothing does untraced."""
    runs = []
    monkeypatch.setattr(harness, "load_reader",
                        lambda name: lambda run: runs.append(run))
    harness.serve(tiny_config("qwen3-32b"), tiny_mix(), seed=SEED,
                  seconds=2.0, trace=trace, chips=1,
                  metrics=[{"name": "capture", "unit": "u"}],
                  t_process=time.perf_counter(), device_peaks=PEAKS)
    (run,) = runs
    assert run.family is family_of(tiny_config("qwen3-32b"))
    assert run.rounds and run.prefills
    if not trace:
        assert run.spans == []
        assert all(r.meta == {} for r in run.rounds + run.prefills)
        return
    assert {"engine.decode", "engine.prefill"} <= {s.name for s in run.spans}
    assert all(run.t0 <= s.t0 < run.t1 for s in run.spans)
    for r in run.rounds:
        assert (r.meta["rows"], r.meta["keys"]) == (len(r.keys), sum(r.keys))
        assert r.meta["attn"] == "xla" and r.meta["kv_blocks"] > 0
    for p in run.prefills:
        assert p.meta["n"] == p.n and p.meta["pad_to"] >= p.n


def test_every_configuration_names_a_family_that_loads():
    files = sorted((ROOT / "bench" / "configs").glob("*.json"))
    assert files
    for f in files:
        spec = json.loads(f.read_text())
        assert family_of(spec).dims_of(spec).vocab > 0


def test_unknown_family_names_the_known_ones():
    with pytest.raises(ValueError, match="dense_gqa"):
        family_of({"name": "x", "family": "no_such_family"})
