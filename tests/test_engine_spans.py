"""The serving engine's own spans (``Engine(tracer=...)``): off by
default and then recording nothing; on, one closed span tree per call
with its children inside it, the sizes of the call in its meta, the
backend compiles inside it, and the same spans as annotations in a
profiler trace.  Runs at the benchmark's tiny CPU shapes
(``bench/tests/tiny.py``), once per configuration where a case repeats."""
import collections
import glob
import itertools
import time

import jax
import numpy as np
import pytest

from bench.dims import dims_of
from bench.harness import model_config
from bench.tests.tiny import tiny_config
from repro.models import lm
from repro.obs.tracer import ROOT, Tracer
from repro.serving.engine import Engine

FAMILIES = ["mistral-nemo-12b", "qwen3-32b"]
PREFILL_CHILDREN = ["engine.prefill.prep", "engine.prefill.dispatch",
                    "engine.prefill.kv_write"]
DECODE_CHILDREN = ["engine.decode.prep", "engine.decode.dispatch",
                   "engine.decode.book", "engine.decode.sync"]


@pytest.fixture(scope="module", params=FAMILIES)
def model(request):
    spec = tiny_config(request.param)
    cfg = model_config(spec, dims_of(spec))
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0)), spec["engine"]


def _engine(model, tracer=None):
    cfg, params, e = model
    return Engine(cfg, params, n_slots=e["n_slots"], max_len=e["max_len"],
                  pool_blocks=e["pool_blocks"], block_size=e["block_size"],
                  tracer=tracer)


def _serve(eng, lengths, rounds, seed=0):
    """Admit one prompt per length, then run ``rounds`` decode rounds
    over all of them; returns every token served, in order."""
    rng = np.random.default_rng(seed)
    nxt = {}
    for i, n in enumerate(lengths):
        ids = rng.integers(1, eng.cfg.vocab, size=n).astype(np.int32)
        nxt[eng.start_session(f"s{i}", ids[:-1], cached_hit=False)] = \
            int(ids[-1])
    served = []
    for _ in range(rounds):
        out = eng.decode(dict(nxt))
        for s in nxt:
            nxt[s] = out[s][0]
            served.append(out[s][0])
    return served


def _children(tracer, span):
    return [c for c in tracer.spans if c.parent_id == span.span_id]


def test_no_tracer_records_nothing_and_serves_the_same_tokens(
        model, monkeypatch):
    traced = _serve(_engine(model, Tracer(clock=time.perf_counter)),
                    [40, 21, 70], rounds=4)

    def refuse(*a, **k):
        raise AssertionError("an engine without a tracer recorded")

    eng = _engine(model)
    assert eng.tracer is None
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(Tracer, "begin", refuse)
    assert _serve(eng, [40, 21, 70], rounds=4) == traced


def test_one_span_tree_per_call(model):
    tr = Tracer(clock=time.perf_counter)
    eng = _engine(model, tr)
    lengths, rounds = [40, 21, 70], 3
    _serve(eng, lengths, rounds)
    tr.check_closed()
    tops = [sp for sp in tr.spans if sp.parent_id == ROOT]
    assert [sp.name for sp in tops] == \
        ["engine.prefill"] * len(lengths) + ["engine.decode"] * rounds
    assert all(sp.track == "engine" and sp.status == "ok"
               for sp in tr.spans)
    for sp in tr.spans:
        want = {"engine.prefill": PREFILL_CHILDREN,
                "engine.decode": DECODE_CHILDREN}.get(sp.name, [])
        kids = _children(tr, sp)
        assert [c.name for c in kids] == want
        for c in kids:
            assert sp.t0 <= c.t0 <= c.t1 <= sp.t1
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0
    # prefill meta: the tokens prefilled (the prompt but its last token)
    # and the compile bucket they were padded to
    for i, (sp, n) in enumerate(zip(tops, lengths)):
        assert sp.meta["sid"] == f"s{i}"
        assert sp.meta["n"] == n - 1
        assert sp.meta["pad_to"] >= n - 1
        assert sp.meta["pad_to"] % eng._prefill_quantum == 0
    # decode meta: live rows, the keys their new tokens attend, the
    # blocks that holds and how it is attended (the CPU's reference)
    block = eng.pool.block
    for r, sp in enumerate(tops[len(lengths):]):
        assert sp.meta["rows"] == len(lengths)
        assert sp.meta["keys"] == sum(n + r for n in lengths)
        assert sp.meta["kv_blocks"] == sum(-(-(n + r) // block)
                                           for n in lengths)
        assert sp.meta["attn"] == "xla"


def test_compiles_counted_inside_the_span(model):
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        tr = Tracer(clock=time.perf_counter)
        eng = _engine(model, tr)
        # a bucket no other case of this file prefills
        _serve(eng, [200], rounds=1)
        eng.release_session("s0")
        _serve(eng, [200], rounds=1, seed=1)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    first, repeat = [sp for sp in tr.spans if sp.name == "engine.prefill"]
    assert first.meta["compiles"] >= 1
    assert repeat.meta["compiles"] == 0
    dispatch = [c for c in _children(tr, first)
                if c.name == "engine.prefill.dispatch"]
    assert dispatch[0].meta["compiles"] >= 1
    second_round = [sp for sp in tr.spans if sp.name == "engine.decode"][1]
    assert second_round.meta["compiles"] == 0


def test_program_names_hold_what_the_benchmark_keys_on(model):
    """The benchmark finds the prefill and paged decode programs in a
    device trace by these substrings of their module names."""
    eng = _engine(model)
    pool = eng.pool
    ids = np.zeros((1, 32), np.int32)
    prefill = eng._jit_prefill.lower(eng.params, ids, pad_to=32)
    n = eng.n_slots
    row = np.zeros((n,), np.int32)
    decode = eng._jit_paged_decode.lower(
        eng.params, np.zeros((n, 1), np.int32), pool.k_pool, pool.v_pool,
        np.zeros((n, eng.max_nb), np.int32), row, row, row)
    assert "prefill" in prefill.compiler_ir().operation.attributes[
        "sym_name"].value
    assert "paged_decode" in decode.compiler_ir().operation.attributes[
        "sym_name"].value


def test_spans_reach_the_profiler_trace(tmp_path):
    spec = tiny_config("mistral-nemo-12b")
    cfg = model_config(spec, dims_of(spec))
    eng_model = (cfg, lm.init_params(cfg, jax.random.PRNGKey(0)),
                 spec["engine"])
    tr = Tracer(clock=time.perf_counter)
    eng = _engine(eng_model, tr)
    _serve(eng, [40], rounds=1)           # compile outside the trace
    eng.release_session("s0")
    n_before = len(tr.spans)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, [40, 21], rounds=3, seed=2)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for ev in itertools.chain.from_iterable(
                    ln.events for ln in plane.lines):
                if ev.name.startswith("engine."):
                    seen[ev.name] += 1
    want = collections.Counter(sp.name for sp in tr.spans[n_before:])
    assert seen == want
    assert want["engine.decode"] == 3 and want["engine.prefill"] == 2


def test_tracer_span_stamps_the_injected_clock():
    ticks = iter(range(10, 100))
    tr = Tracer(clock=lambda: float(next(ticks)))
    with tr.span("engine", "outer", k=1) as outer:
        with tr.span("engine", "inner", parent=outer):
            pass
    with pytest.raises(KeyError):
        with tr.span("engine", "fails"):
            raise KeyError("x")
    tr.check_closed()
    assert [(sp.name, sp.t0, sp.t1, sp.status) for sp in tr.spans] == [
        ("outer", 10.0, 13.0, "ok"), ("inner", 11.0, 12.0, "ok"),
        ("fails", 14.0, 15.0, "error")]
    assert tr.spans[0].meta == {"k": 1}
    assert tr.spans[1].parent_id == outer
    with pytest.raises(ValueError):
        with Tracer().span("engine", "no clock"):
            pass
