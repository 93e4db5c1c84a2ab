"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three lists of ``[name, start_ns, duration_ns]``: the first TPU's device
operations (line ``XLA Ops``), its program executions (line ``XLA
Modules``), and the harness's own host spans (``TraceAnnotation`` names).
Everything after that is plain arithmetic on those lists, and is checked on
a small recorded trace under ``testdata/``.

A window holds millions of device ops, so they are read once into arrays
(``Ops``) and each reduction works on those: ``Ops.clip`` cuts the ops to a
run of disjoint intervals (the window, or each step), ``cover`` merges the
pieces in each interval, and ``Busy`` keeps the window's cover and each op
name's time, from which the busy time, the idle gaps and the breakdown are
read.
"""

from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

import numpy as np

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


def steps(trace: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """``(start, end)`` of each served step's execution inside the window."""
    return [(s, s + d) for name, s, d in trace["modules"]
            if name.split("(")[0] == STEP_MODULE and lo <= s < hi]


# ------------------------------------------------------------ ops as arrays
@dataclasses.dataclass(frozen=True)
class Pieces:
    """Device ops cut to a run of disjoint intervals: each piece's interval,
    op name (an index into ``Ops.names``), start and end, in the order of
    interval, then start."""

    interval: np.ndarray
    name_id: np.ndarray
    start: np.ndarray
    end: np.ndarray

    def __getitem__(self, mask: np.ndarray) -> Pieces:
        return Pieces(self.interval[mask], self.name_id[mask],
                      self.start[mask], self.end[mask])


@dataclasses.dataclass(frozen=True)
class Ops:
    """A trace's device ops: each distinct name once, and each op's name
    (an index into ``names``), start and end in ns, in the order of start."""

    names: list[str]
    name_id: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, events: list) -> Ops:
        """The ops ``[[name, start_ns, duration_ns], ...]``."""
        ids: dict[str, int] = {}
        n = len(events)
        name_id = np.fromiter((ids.setdefault(name, len(ids))
                               for name, _, _ in events), np.int64, n)
        start = np.fromiter((s for _, s, _ in events), np.float64, n)
        end = start + np.fromiter((d for _, _, d in events), np.float64, n)
        order = np.argsort(start, kind="stable")
        return cls(list(ids), name_id[order], start[order], end[order])

    def clip(self, intervals: list[tuple[float, float]]) -> Pieces:
        """Each op that overlaps one of ``intervals`` (disjoint, in order),
        cut to it; an op over several gives a piece in each. An op overlaps
        ``[lo, hi)`` where it starts before ``hi`` and ends after ``lo``."""
        bounds = np.asarray(intervals, np.float64).reshape(-1, 2)
        los, his = bounds[:, 0], bounds[:, 1]
        first = np.searchsorted(his, self.start, side="right")
        count = np.maximum(np.searchsorted(los, self.end, side="left")
                           - first, 0)
        op = np.repeat(np.arange(count.size), count)
        interval = np.repeat(first, count) + np.arange(op.size) - np.repeat(
            np.cumsum(count) - count, count)
        order = np.argsort(interval, kind="stable")
        op, interval = op[order], interval[order]
        return Pieces(interval, self.name_id[op],
                      np.maximum(self.start[op], los[interval]),
                      np.minimum(self.end[op], his[interval]))


def cover(pieces: Pieces) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(interval, start, end)`` of each run of overlapping or touching
    pieces of one interval, in order: the disjoint, sorted cover of each
    interval's pieces."""
    if not pieces.start.size:
        empty = np.empty(0)
        return np.empty(0, np.int64), empty, empty
    # the intervals are disjoint and in order, so the reach of earlier
    # intervals' pieces never passes a later interval's start
    reach = np.maximum.accumulate(pieces.end)
    new = np.ones(pieces.start.size, bool)
    new[1:] = (pieces.start[1:] > reach[:-1]) | (
        pieces.interval[1:] != pieces.interval[:-1])
    first = np.flatnonzero(new)
    last = np.append(first[1:], new.size) - 1
    return pieces.interval[first], pieces.start[first], reach[last]


def covered_ns(pieces: Pieces, n_intervals: int) -> np.ndarray:
    """The time in each interval in which some piece lies."""
    interval, start, end = cover(pieces)
    return np.bincount(interval, weights=end - start, minlength=n_intervals)


# ------------------------------------------------------- the traced window
@dataclasses.dataclass(frozen=True)
class Busy:
    """The device ops of the window ``[lo, hi)``, reduced once: the cover of
    the ops, cut to the window, and the time of each op name in it."""

    lo: float
    hi: float
    starts: np.ndarray          # the cover, sorted and disjoint
    ends: np.ndarray
    name_ns: dict[str, float]   # each name of an op in the window

    @classmethod
    def of(cls, ops: Ops, lo: float, hi: float) -> Busy:
        pieces = ops.clip([(lo, hi)])
        _, starts, ends = cover(pieces)
        ns = np.bincount(pieces.name_id, weights=pieces.end - pieces.start,
                         minlength=len(ops.names))
        return cls(lo, hi, starts, ends, {
            ops.names[i]: float(ns[i])
            for i in np.unique(pieces.name_id).tolist()})

    @property
    def busy_ns(self) -> float:
        """Time in the window in which some device op ran."""
        return float(np.sum(self.ends - self.starts))

    def idle_intervals(self) -> list[tuple[float, float]]:
        """The gaps in the window in which no device op ran."""
        lo = np.append(self.lo, self.ends)
        hi = np.append(self.starts, self.hi)
        gap = hi > lo
        return list(zip(lo[gap].tolist(), hi[gap].tolist(), strict=True))

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` device ops with the most time in the window, in
        seconds."""
        return _ranked(self.name_ns, n)

    def idle_by_span(self, spans: list, n: int = 10) -> list[list]:
        """Device idle time in the window, summed by the host span of
        ``spans`` (a trace's, sorted by start) open at the middle of each
        gap, in seconds, the largest first. The harness's spans inside the
        window follow one another without nesting."""
        spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
        gaps = np.array(self.idle_intervals(), np.float64).reshape(-1, 2)
        mid = 0.5 * (gaps[:, 0] + gaps[:, 1])
        start = np.array([sp[1] for sp in spans], np.float64)
        end = start + np.array([sp[2] for sp in spans], np.float64)
        i = np.searchsorted(start, mid, side="right") - 1
        inside = i >= 0
        inside[inside] = mid[inside] < end[i[inside]]
        total: dict[str, float] = {}
        for j, ok, ns in zip(i.tolist(), inside.tolist(),
                             (gaps[:, 1] - gaps[:, 0]).tolist(), strict=True):
            name = spans[j][0] if ok else "none"
            total[name] = total.get(name, 0.0) + ns
        return _ranked(total, n)


def _ranked(total: dict[str, float], n: int) -> list[list]:
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]
