"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three lists of ``[name, start_ns, duration_ns]``: the first TPU's device
operations (line ``XLA Ops``), its program executions (line ``XLA
Modules``), and the harness's own host spans (``TraceAnnotation`` names).
Everything after that is plain arithmetic on those lists, and is checked on
a small recorded trace under ``testdata/``.
"""

from __future__ import annotations

import bisect
import collections
import glob
from pathlib import Path

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_MODULE = "jit_serve_step"
WINDOW_SPAN = "window"


def extract(logdir: str | Path, span_names: tuple) -> dict:
    """The device ops, device program executions and host spans of the one
    trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(str(Path(logdir) / "plugins" / "profile" / "*" /
                      "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    out: dict = {"ops": [], "modules": [], "spans": []}
    wanted = set(span_names)
    for plane in data.planes:
        if plane.name.split(" ")[0] == DEVICE_PLANE:
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    out[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events if e.name in wanted]
    for key in out:
        out[key].sort(key=lambda ev: ev[1])
    return out


def window(trace: dict) -> tuple[float, float] | None:
    """``[start, end)`` of the harness's window span, in ns."""
    spans = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if len(spans) != 1:
        return None
    return spans[0][1], spans[0][1] + spans[0][2]


def _clip(events: list, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of ``intervals``."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: dict, lo: float, hi: float) -> float:
    """Time in ``[lo, hi)`` in which some device op ran."""
    return sum(e - s for s, e in union(_clip(trace["ops"], lo, hi)))


def idle_intervals(trace: dict, lo: float, hi: float
                   ) -> list[tuple[float, float]]:
    """The gaps in ``[lo, hi)`` in which no device op ran."""
    gaps, t = [], lo
    for s, e in union(_clip(trace["ops"], lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def steps(trace: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """``(start, end)`` of each served step's execution inside the window."""
    return [(s, s + d) for name, s, d in trace["modules"]
            if name.split("(")[0] == STEP_MODULE and lo <= s < hi]


def _span_index(trace: dict) -> tuple[list, list]:
    spans = [sp for sp in trace["spans"] if sp[0] != WINDOW_SPAN]
    return spans, [sp[1] for sp in spans]


def _open(spans: list, starts: list, t: float) -> str:
    """The host span open at ``t``; the harness's spans inside the window
    follow one another without nesting."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i][0] if i >= 0 and t < starts[i] + spans[i][2] else "none"


def top_ops(trace: dict, lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """The ``n`` device ops with the most time in the window, in seconds."""
    total: dict[str, float] = collections.defaultdict(float)
    for name, s, e in ((name, max(s, lo), min(s + d, hi))
                       for name, s, d in trace["ops"] if s < hi and s + d > lo):
        total[name] += e - s
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_by_span(trace: dict, lo: float, hi: float, n: int = 10
                 ) -> list[list]:
    """Device idle time in the window, summed by the host span open at the
    middle of each gap, in seconds, the largest first."""
    spans, starts = _span_index(trace)
    total: dict[str, float] = collections.defaultdict(float)
    for s, e in idle_intervals(trace, lo, hi):
        total[_open(spans, starts, 0.5 * (s + e))] += e - s
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]
