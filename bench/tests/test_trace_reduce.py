"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (five runs of one jitted matmul between the two
window annotations)."""
from pathlib import Path

import pytest

from bench import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_and_clip():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T._clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_program_name():
    assert T.program_name("jit_paged_decode_fn(1234)") == "jit_paged_decode_fn"
    assert T.program_name("jit_prefill_fn") == "jit_prefill_fn"


def test_idle_gaps_named_by_host_span():
    s = T.TraceSummary(window_s=1.0, busy_s=0.5, module_s={"a": 0.4, "b": 0.1},
                       gaps=[(0.0, 100.0), (300.0, 350.0), (500.0, 900.0)],
                       window_ns=(0.0, 1e3), n_devices=1)
    host = [("round", 0.0, 120.0), ("prefill", 480.0, 950.0)]
    top = s.idle_gaps(host, top=2)
    assert [k for k, _ in top] == ["prefill", "round"]
    assert [v for _, v in top] == pytest.approx([400e-9, 100e-9])
    assert s.idle_gaps(host)[-1][0] == "host"
    assert s.top_modules(1) == [["a", 0.4]]


def test_host_spans_move_onto_the_trace_clock():
    spans = T.host_spans_on_trace([("round", 10.5, 10.75)], 10.0, 1000.0)
    assert spans == [("round", 1000.0 + 0.5e9, 1000.0 + 0.75e9)]


def test_recorded_trace():
    s = T.reduce_trace(str(DATA))
    assert s is not None and s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    (name, secs), = [kv for kv in s.top_modules() if "small_matmul" in kv[0]]
    # five runs of one program; the device ran nothing else in between
    assert secs == pytest.approx(s.busy_s, rel=0.01)
    assert len(s.gaps) >= 5
    assert abs(sum(b - a for a, b in s.gaps) * 1e-9
               - (s.window_s - s.busy_s)) < 1e-6
