"""Serve one cell once and check what it served.

The system under test is the program's serving engine,
``repro.serving.engine.Engine``, one per chip, paged, driven through its
public calls: ``start_session`` prefills a request's prompt but its last
token into pool blocks, ``decode`` runs one batched round over every
live row (the first round of a request feeds its prompt's last token),
``release_session`` frees its blocks.  The loop here is plain continuous
batching: requests join a FIFO queue at their scheduled arrival; between
rounds every queued request that finds a free slot is admitted (one
batch-1 prefill each); then one decode round runs.  Each round ends in
the engine's ``argmax`` readback, so a token's ``perf_counter`` stamp is
after the device produced it.

The program's ``ServingRuntime`` is not on this path: after a full
prefill it feeds the context's last token again as the first decode
input (the prefill's logits are dropped), so what it serves is not the
model's greedy continuation and the comparison in ``check.py`` fails
it.  The engine's calls serve that continuation when the prompt's last
token is held back from the prefill, as here.

Set-up is everything before the window: weights made on the device, the
engine built, one request of every prompt size the mix uses (so every
program the window runs is compiled or read from the persistent cache),
and ``warm_s`` seconds of the schedule itself.  The window's population
is every request that arrives in it; the loop serves on past the close,
with the schedule's later arrivals, until each of them has finished (at
most ``DRAIN_S``).  Then the program's state is freed and the reference
(``bench/check.py``) runs over a sample of what the window served.

What depends on the architecture (shapes, the program's configuration,
the weights' layout, the reference and the counts of bytes and FLOPs)
comes from the configuration's family (``bench/families``).  A traced
run (``--trace 1``) builds the engine with a tracer, and hands the meta
of each call's engine span and the window's spans to the metric
readers; an untraced run builds it without one."""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import math
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from bench import check, trace_reduce, weights as W  # noqa: E402
from bench.families import family_of  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from bench.traffic import Request, schedule, seed_words, sizes  # noqa: E402

METRICS_DIR = Path(__file__).resolve().parent / "metrics"
TRACE_SECONDS = 6.0      # length of the traced part of a --trace 1 window
DRAIN_S = 60.0           # a window request unfinished by then has failed


# --------------------------------------------------------------------------
# what a run records
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ReqRec:
    req: Request
    arrival: float               # perf_counter of the scheduled arrival
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_t: List[float] = dataclasses.field(default_factory=list)

    @property
    def complete(self) -> bool:
        return len(self.tokens) >= self.req.n_out


@dataclasses.dataclass
class RoundRec:
    start: float
    end: float
    keys: List[int]              # keys each live row's new token attends
    meta: dict = dataclasses.field(default_factory=dict)   # see RunRecord


@dataclasses.dataclass
class PrefillRec:
    start: float                 # host span of the call (not synced:
    end: float                   # the next round's readback is)
    n: int                       # tokens prefilled
    meta: dict = dataclasses.field(default_factory=dict)   # see RunRecord


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read (``metrics/<name>.py``): per
    request its arrival, token times and sizes; per decode round and
    prefill its host span and sizes; the window's bounds; the compiles
    inside it; the configuration's family and shapes; and, in a traced
    run, the reduced trace, the engine's spans that start in the window
    (``spans``, ``repro.obs.tracer.Span`` on the host clock) and per
    round and prefill the meta of the engine's span of that call
    (``RoundRec.meta``: ``engine.decode``'s; ``PrefillRec.meta``:
    ``engine.prefill``'s; ``{}`` untraced).  A metric added later reads
    these and needs no harness edit."""
    family: ModuleType           # bench/families/<family>.py
    dims: object                 # the family's Dims
    peaks: dict
    t_process: float
    t0: float
    t1: float
    requests: List[ReqRec]
    rounds: List[RoundRec]
    prefills: List[PrefillRec]
    compiles_window: int
    trace: Optional[trace_reduce.TraceSummary] = None
    trace_pc: Optional[Tuple[float, float]] = None
    spans: List = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    @property
    def population(self) -> List[ReqRec]:
        """Requests that arrived in the window."""
        return [r for r in self.requests if self.t0 <= r.arrival < self.t1]

    def in_trace(self, start: float, end: float) -> bool:
        return self.trace_pc is not None \
            and self.trace_pc[0] <= start and end <= self.trace_pc[1]


def call_meta(tracer, since: int, name: str) -> dict:
    """The meta of the first span named ``name`` that ``tracer`` recorded
    from its ``since``-th span on: the engine's span of the call made
    since then.  ``{}`` without a tracer."""
    if tracer is None:
        return {}
    return next((dict(sp.meta) for sp in tracer.spans[since:]
                 if sp.name == name), {})


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100) of every value."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


# --------------------------------------------------------------------------
# compile counting (JAX's own events)
# --------------------------------------------------------------------------
class _Compiles:
    """Backend compiles in this process.  JAX's event listeners are
    process-wide and cannot be removed, so the count is too: a run reads
    its difference across the window."""
    n = 0
    _registered = False

    @classmethod
    def install(cls) -> None:
        if not cls._registered:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._registered = True

    @classmethod
    def _on(cls, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.n += 1


# --------------------------------------------------------------------------
# the serving loop
# --------------------------------------------------------------------------
class Server:
    """Open-loop continuous batching over one engine (see the module
    docstring)."""

    def __init__(self, engine, requests: Sequence[Request], *, warm_s: float,
                 seconds: float, trace_dir: Optional[str]):
        self.eng = engine
        self.schedule = list(requests)
        self.warm_s, self.seconds = warm_s, seconds
        self.trace_dir = trace_dir
        self.recs: List[ReqRec] = []
        self.rounds: List[RoundRec] = []
        self.prefills: List[PrefillRec] = []
        self.host_spans: List[Tuple[str, float, float]] = []
        self.t0 = self.t1 = 0.0
        self.compiles0 = self.compiles1 = 0
        self.trace_pc: Optional[Tuple[float, float]] = None
        self._tracing = False

    def run(self) -> None:
        eng = self.eng
        tracer = eng.tracer
        spans = tracer.spans if tracer is not None else []
        base = time.perf_counter()
        queue: collections.deque = collections.deque()
        live: Dict[int, ReqRec] = {}
        nxt: Dict[int, int] = {}
        i, phase = 0, "warm"
        while True:
            now = time.perf_counter()
            # the device has finished all it was given: a phase or the
            # trace turns here
            if phase == "warm" and now >= base + self.warm_s:
                phase, self.t0 = "window", now
                self.t1 = now + self.seconds
                self.compiles0 = _Compiles.n
                if self.trace_dir is not None:
                    self._start_trace()
            if self._tracing and now >= self.trace_pc[0] + TRACE_SECONDS:
                self._stop_trace()
            if phase == "window" and now >= self.t1:
                phase = "drain"
                self.compiles1 = _Compiles.n
                if self._tracing:
                    self._stop_trace()
            if phase == "drain" and (now >= self.t1 + DRAIN_S or all(
                    r.complete for r in self.recs if r.arrival < self.t1)):
                return
            while i < len(self.schedule) \
                    and base + self.schedule[i].arrival <= now:
                rec = ReqRec(self.schedule[i], base + self.schedule[i].arrival)
                self.recs.append(rec)
                queue.append(rec)
                i += 1
            while queue and eng.free_slot() is not None:
                rec = queue.popleft()
                prompt = rec.req.prompt
                since = len(spans)
                t = time.perf_counter()
                slot = eng.start_session(rec.req.rid,
                                         np.asarray(prompt[:-1], np.int32),
                                         cached_hit=False)
                live[slot], nxt[slot] = rec, prompt[-1]
                end = time.perf_counter()
                self.prefills.append(PrefillRec(
                    t, end, len(prompt) - 1,
                    call_meta(tracer, since, "engine.prefill")))
                self._span("prefill", t, end)
            if live:
                since = len(spans)
                t = time.perf_counter()
                out = eng.decode(dict(nxt), n_steps=1)
                end = time.perf_counter()
                keys = []
                for slot, rec in list(live.items()):
                    tok = int(out[slot][0])
                    rec.tokens.append(tok)
                    rec.token_t.append(end)
                    keys.append(len(rec.req.prompt) + len(rec.tokens) - 1)
                    nxt[slot] = tok
                    if rec.complete:
                        eng.release_session(rec.req.rid)
                        del live[slot], nxt[slot]
                self.rounds.append(RoundRec(
                    t, end, keys, call_meta(tracer, since, "engine.decode")))
                self._span("round", t, end)
            elif i < len(self.schedule):
                wake = base + self.schedule[i].arrival
                if phase == "warm":
                    wake = min(wake, base + self.warm_s)
                elif self._tracing:
                    wake = min(wake, self.trace_pc[0] + TRACE_SECONDS)
                t = time.perf_counter()
                if wake > t:
                    time.sleep(wake - t)
                self._span("idle", t, time.perf_counter())
            elif phase == "warm":
                raise RuntimeError("the schedule ended before the window")
            else:
                time.sleep(0.001)

    def _span(self, kind: str, start: float, end: float) -> None:
        if self._tracing:
            self.host_spans.append((kind, start, end))

    def _start_trace(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_START):
            pc = time.perf_counter()
        self.trace_pc = (pc, pc)
        self._tracing = True

    def _stop_trace(self) -> None:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_STOP):
            pc = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_pc = (self.trace_pc[0], pc)
        self._tracing = False


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def model_config(spec: dict, d):
    """The program's ``ModelConfig`` for a configuration file and its
    shapes ``d``: its family's ``program_config``."""
    return family_of(spec).program_config(spec, d)


def check_layout(params, mcfg) -> None:
    """The weights' tree, shapes and dtypes must be the program's."""
    from repro.models import lm
    want = jax.eval_shape(lambda: lm.init_params(mcfg, jax.random.PRNGKey(0)))
    got = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) \
            or jax.tree_util.tree_leaves(want) != \
            jax.tree_util.tree_leaves(got):
        raise RuntimeError("benchmark weight layout differs from the "
                           "program's lm.init_params layout")


def load_reader(name: str) -> Callable:
    """``metrics/<name>.py``'s ``read(run) -> value or None``."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def warm_shapes(eng, mix: dict, vocab: int, seed: int) -> None:
    """One request of every prompt size of the mix, prefilled as the
    window prefills and decoded for one round, so every program the
    window runs is compiled (or read from the persistent cache)."""
    rng = np.random.default_rng(seed_words(seed) + [1])
    todo = sorted(set(sizes(mix["prompt"])))
    while todo:
        batch, todo = todo[:eng.n_slots], todo[eng.n_slots:]
        slots = {}
        for n in batch:
            sid = f"warm-{n}"
            ids = rng.integers(1, vocab, size=n - 1).astype(np.int32)
            slots[eng.start_session(sid, ids, cached_hit=False)] = 1
        eng.decode(slots, n_steps=1)
        for n in batch:
            eng.release_session(f"warm-{n}")


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def serve(config: dict, mix: dict, *, seed: int, seconds: float,
          trace: bool, chips: int, metrics: Sequence[dict],
          t_process: float, log=None,
          device_peaks: Optional[dict] = None):
    """Serve the cell for ``seconds``.  Returns the result line's dict
    without ``correct`` and ``check``, the requests finished among the
    window's population as ``check.ServedStep``s, the shapes and the
    devices; the program's state is freed.  ``device_peaks`` stands in
    for the published table only where a test serves on a device that
    is not in it."""
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.tracer import Tracer
    from repro.serving.engine import Engine

    log = log or (lambda *a: None)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _Compiles.install()
    devs = jax.devices()[:chips]
    if chips != 1:
        raise ValueError("the serving loop drives one engine on one chip")
    kind = devs[0].device_kind
    fam = family_of(config)
    d = fam.dims_of(config)
    mcfg = model_config(config, d)
    eng_spec = config["engine"]

    params = W.make_weights(fam, d, seed, devs[0])
    check_layout(params, mcfg)
    log(f"weights made: {W.n_params(fam, d)} params, "
        f"{time.perf_counter() - t_process:.1f} s")
    eng = Engine(mcfg, params, n_slots=eng_spec["n_slots"],
                 max_len=eng_spec["max_len"],
                 pool_blocks=eng_spec["pool_blocks"],
                 block_size=eng_spec["block_size"], device=devs[0],
                 tracer=Tracer(clock=time.perf_counter) if trace else None)
    del params
    warm_shapes(eng, mix, d.vocab, seed)
    log(f"shapes warmed: {_Compiles.n} compiles, "
        f"{time.perf_counter() - t_process:.1f} s")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    srv = Server(eng, schedule(mix, seed, d.vocab, seconds),
                 warm_s=float(mix["warm_s"]), seconds=seconds, trace_dir=tmp)
    # what set-up built lives on: keep the collector from rescanning it
    gc.collect()
    gc.freeze()
    srv.run()
    mem_peak = memory_peak(devs)
    log(f"window: {srv.t1 - srv.t0:.2f} s, {len(srv.rounds)} rounds, "
        f"{len(srv.recs)} requests")

    run = RunRecord(family=fam, dims=d, peaks=device_peaks or peaks(kind),
                    t_process=t_process, t0=srv.t0,
                    t1=srv.t1, requests=srv.recs, rounds=srv.rounds,
                    prefills=srv.prefills,
                    compiles_window=srv.compiles1 - srv.compiles0,
                    trace_pc=srv.trace_pc)
    if eng.tracer is not None:
        run.spans = [sp for sp in eng.tracer.spans
                     if srv.t0 <= sp.t0 < srv.t1]
    host_spans: List = []
    if tmp is not None:
        path = trace_reduce.find_trace(tmp)
        run.trace = trace_reduce.reduce_trace(path) if path else None
        if run.trace is not None:
            host_spans = trace_reduce.host_spans_on_trace(
                srv.host_spans, srv.trace_pc[0], run.trace.window_ns[0])
        shutil.rmtree(tmp, ignore_errors=True)

    values = {}
    for m in metrics:
        v = load_reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    pop = run.population
    served = [check.ServedStep(list(r.req.prompt), list(r.tokens))
              for r in pop if r.complete]

    # free the program's state before the reference takes the chip
    del srv, eng
    gc.unfreeze()
    gc.collect()

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": mem_peak}
    out = {"attempted": len(pop),
           "failed": sum(1 for r in pop if not r.complete),
           "metrics": values, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_modules(),
                            "idle_gaps": run.trace.idle_gaps(host_spans)}
    return out, served, d, devs


def checked_sample(served: Sequence[check.ServedStep], seed: int
                   ) -> List[check.ServedStep]:
    return check.sample(served, np.random.default_rng(seed_words(seed) + [2]))


def serve_and_check(config: dict, mix: dict, *, seed: int, **kw) -> dict:
    """Serve the cell (see ``serve``), then compare a sample of what the
    window served with the reference.  Returns the result line's dict:
    ``correct`` first, ``check`` (each number compared, with its limit)
    last."""
    out, served, d, devs = serve(config, mix, seed=seed, **kw)
    log = kw.get("log") or (lambda *a: None)
    t = time.perf_counter()
    fam = family_of(config)
    weights = W.make_weights(fam, d, seed, devs[0])
    sample = checked_sample(served, seed)
    gap = check.widest_gap(fam.logits_at, weights, d, sample)
    del weights
    n_tok = sum(len(s.served) for s in sample)
    log(f"reference: {len(sample)} requests, {n_tok} tokens, "
        f"{time.perf_counter() - t:.1f} s")
    limit = float(config["check"]["max_logit_gap"])
    correct = bool(sample) and out["failed"] == 0 and gap <= limit
    out = {"correct": correct, **out, "compared_tokens": n_tok}
    out["check"] = {"max_logit_gap": {"value": gap, "limit": limit},
                    "unfinished": {"value": out["failed"], "limit": 0}}
    return out
