"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: device busy time, device time per XLA program, and the idle
gaps, each labelled by what the host was doing.

The host side comes from two annotations the harness writes
(``bench_window_start`` / ``bench_window_stop``, which bound the traced
window) and from its own spans (prefill, round, idle), taken on ``perf_counter`` and
moved onto the trace's clock by the start annotation."""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_START = "bench_window_start"
WINDOW_STOP = "bench_window_stop"
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the devices
    module_s: Dict[str, float]          # device seconds per XLA program
    gaps: List[Tuple[float, float]]     # idle (start_ns, end_ns), device 0
    window_ns: Tuple[float, float]
    n_devices: int

    def idle_gaps(self, host: Sequence[Tuple[str, float, float]],
                  top: int = 10) -> List[List]:
        """The ``top`` longest idle gaps, each named by the host span
        (kind, start_ns, end_ns) that covers its middle."""
        out = []
        for a, b in sorted(self.gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = 0.5 * (a + b)
            name = next((k for k, s, e in host if s <= mid <= e), "host")
            out.append([name, (b - a) * 1e-9])
        return out

    def top_modules(self, top: int = 10) -> List[List]:
        ranked = sorted(self.module_s.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:top]]


def program_name(event_name: str) -> str:
    """XLA module events carry a run id: ``jit_f(123)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def find_trace(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _annotation(planes, name: str) -> Optional[float]:
    for p in planes:
        if not p.name.startswith("/host"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == name:
                    return float(ev.start_ns)
    return None


def reduce_trace(path: str) -> Optional[TraceSummary]:
    """None when the trace holds no window or no device activity."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    t0 = _annotation(planes, WINDOW_START)
    t1 = _annotation(planes, WINDOW_STOP)
    if t0 is None or t1 is None or t1 <= t0:
        return None
    busy, module_s, gaps0 = [], {}, None
    for p in planes:
        if not p.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in p.lines}
        op_line = lines.get(_OPS_LINE) or lines.get(_MODULES_LINE)
        if op_line is None:
            continue
        ops = _clip([(float(e.start_ns), float(e.start_ns + e.duration_ns))
                     for e in op_line.events], t0, t1)
        if not ops:
            continue
        u = _union(ops)
        busy.append(sum(e - s for s, e in u))
        if gaps0 is None:
            edges = [t0] + [x for iv in u for x in iv] + [t1]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
        mod = lines.get(_MODULES_LINE)
        for e in (mod.events if mod is not None else ()):
            for s, f in _clip([(float(e.start_ns),
                                float(e.start_ns + e.duration_ns))], t0, t1):
                k = program_name(e.name)
                module_s[k] = module_s.get(k, 0.0) + (f - s) * 1e-9
    if not busy:
        return None
    n = len(busy)
    return TraceSummary(window_s=(t1 - t0) * 1e-9,
                        busy_s=sum(busy) / n * 1e-9,
                        module_s=module_s, gaps=gaps0 or [],
                        window_ns=(t0, t1), n_devices=n)


def host_spans_on_trace(spans: Sequence[Tuple[str, float, float]],
                        pc_at_start: float, trace_start_ns: float
                        ) -> List[Tuple[str, float, float]]:
    """Move host spans (kind, start, end in perf_counter seconds) onto
    the trace's clock, given the perf_counter reading taken as the
    start annotation was written."""
    off = trace_start_ns - pc_at_start * 1e9
    return [(k, s * 1e9 + off, e * 1e9 + off) for k, s, e in spans]
