"""Trace reduction, checked on a small trace.

``testdata/trace_rm2_small.json`` is in the layout ``trace.extract`` keeps:
device ops, ``jit_serve_step`` executions and the harness's host spans, for
four dispatches of a window. It is hand-built in that layout (ops of uneven
length with idle gaps between them, inside the step spans); no trace of
the chip was recorded with this benchmark yet. The expected numbers are
worked out here by brute force over elementary time segments,
independently of the reduction's interval arithmetic.
"""

import json
from pathlib import Path

import jax
import pytest
from chipbench import trace

HERE = Path(__file__).resolve().parent
DATA = json.loads((HERE / "testdata" / "trace_rm2_small.json").read_text())
TRACE = DATA["trace"]


def _segments(events, lo, hi):
    """Elementary segments of [lo, hi) cut at every event boundary, each with
    whether some event covers it."""
    cuts = sorted({lo, hi} | {t for _, s, d in events for t in (s, s + d)
                              if lo < t < hi})
    return [(a, b, any(s <= a and b <= s + d for _, s, d in events))
            for a, b in zip(cuts[:-1], cuts[1:], strict=True)]


def _busy(lo, hi):
    return trace.Busy.of(trace.Ops.of(TRACE["ops"]), lo, hi)


def test_window_is_the_harness_span():
    lo, hi = trace.window(TRACE)
    (span,) = [s for s in TRACE["spans"] if s[0] == trace.WINDOW_SPAN]
    assert (lo, hi) == (span[1], span[1] + span[2])


def test_busy_is_the_union_of_device_ops():
    lo, hi = trace.window(TRACE)
    want = sum(b - a for a, b, covered in _segments(TRACE["ops"], lo, hi)
               if covered)
    assert _busy(lo, hi).busy_ns == pytest.approx(want, abs=1e-3)
    idle = sum(b - a for a, b in _busy(lo, hi).idle_intervals())
    assert idle + want == pytest.approx(hi - lo, abs=1e-3)
    assert 0 < want < hi - lo


def test_steps_are_the_served_step_executions():
    lo, hi = trace.window(TRACE)
    got = trace.steps(TRACE, lo, hi)
    want = [(s, s + d) for name, s, d in TRACE["modules"]
            if name.startswith(trace.STEP_MODULE) and lo <= s < hi]
    assert got == want and len(got) == DATA["dispatches"]
    assert all(e > s for s, e in got)
    assert all(a[1] <= b[0] for a, b in zip(got[:-1], got[1:], strict=True))


def test_gaps_and_the_span_open_in_each():
    lo, hi = trace.window(TRACE)
    spans = [s for s in TRACE["spans"] if s[0] != trace.WINDOW_SPAN]
    want: dict[str, float] = {}
    gaps = [(a, b) for a, b, covered in _segments(TRACE["ops"], lo, hi)
            if not covered]
    merged = []                 # adjacent idle segments form one gap
    for a, b in gaps:
        if merged and merged[-1][1] == a:
            merged[-1][1] = b
        else:
            merged.append([a, b])
    assert [tuple(g) for g in merged] == pytest.approx(
        _busy(lo, hi).idle_intervals())
    for a, b in merged:
        mid = 0.5 * (a + b)
        open_ = [n for n, s, d in spans if s <= mid < s + d]
        name = open_[0] if open_ else "none"
        want[name] = want.get(name, 0.0) + (b - a) * 1e-9
    assert dict(_busy(lo, hi).idle_by_span(TRACE["spans"], n=100)) \
        == pytest.approx(want)
    assert len(want) > 1


def test_top_ops_sum_each_name_in_the_window():
    lo, hi = trace.window(TRACE)
    top = _busy(lo, hi).top_ops(n=5)
    for name, seconds in top:
        want = sum(min(s + d, hi) - max(s, lo) for n, s, d in TRACE["ops"]
                   if n == name and s < hi and s + d > lo)
        assert seconds == pytest.approx(want * 1e-9)
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)


def test_extract_keeps_the_harness_spans(tmp_path):
    f = jax.jit(lambda x: x * 2.0)
    x = jax.numpy.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = trace.extract(tmp_path, (trace.WINDOW_SPAN, "step"))
    assert [s[0] for s in got["spans"]] == [trace.WINDOW_SPAN, "step"]
    lo, hi = trace.window(got)
    assert lo <= got["spans"][1][1] < hi
