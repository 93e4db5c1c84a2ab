"""One reduction of a traced window reads what a pass per reading read.

The device readings come from arrays made once per run
(``trace.Ops``, ``trace.Busy``, ``scopes.split``). The reference here is the
plain form they replace: a Python pass over the trace's op lists for each
reading, each op clipped to the window or to each step and the intervals
merged by sorting. Both are run on the committed cuts of chip traces and on a
seeded synthetic trace of a thousand steps of several hundred ops, some of
them overlapping and some straddling the bounds of a step or of the window.
"""

import bisect
import collections
import dataclasses
import functools
import json
from pathlib import Path

import numpy as np
import pytest
from chipbench import harness, scopes, trace

HERE = Path(__file__).resolve().parent
SCOPED = json.loads((HERE / "testdata" / "trace_rm2_scoped.json").read_text())
SMALL = json.loads((HERE / "testdata" / "trace_rm2_small.json").read_text())
BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CFG = json.loads((HERE / "configs" / "dlrm_rm2.json").read_text())
PATHS = ("jit(serve_step)/translate/gather",
         "jit(serve_step)/sls/jit(_take)/gather",
         "jit(serve_step)/sls/reduce_sum",
         "jit(serve_step)/interact/cross/dot_general",
         "jit(serve_step)/interact/concatenate",
         "jit(serve_step)/mlp/dot_general",
         "jit(serve_step)/mlp/add",
         "")


# ------------------------------------------- a pass per reading, as before
def _clip(events, lo, hi):
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _busy_ns(tr, lo, hi):
    return sum(e - s for s, e in _union(_clip(tr["ops"], lo, hi)))


def _idle_intervals(tr, lo, hi):
    gaps, t = [], lo
    for s, e in _union(_clip(tr["ops"], lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _ranked(total, n):
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def _top_ops(tr, lo, hi, n):
    total = collections.defaultdict(float)
    for name, s, d in tr["ops"]:
        if s < hi and s + d > lo:
            total[name] += min(s + d, hi) - max(s, lo)
    return _ranked(total, n)


def _idle_by_span(tr, lo, hi, n):
    spans = [sp for sp in tr["spans"] if sp[0] != trace.WINDOW_SPAN]
    starts = [sp[1] for sp in spans]
    total = collections.defaultdict(float)
    for s, e in _idle_intervals(tr, lo, hi):
        t = 0.5 * (s + e)
        i = bisect.bisect_right(starts, t) - 1
        name = spans[i][0] if i >= 0 and t < starts[i] + spans[i][2] \
            else "none"
        total[name] += e - s
    return _ranked(total, n)


def _ops_by_step(tr, steps):
    ops = tr["ops"]
    starts = [op[1] for op in ops]
    longest = max((op[2] for op in ops), default=0)
    out = []
    for lo, hi in steps:
        i = bisect.bisect_left(starts, lo - longest)
        j = bisect.bisect_left(starts, hi)
        out.append([(name, max(s, lo), min(s + d, hi))
                    for name, s, d in ops[i:j] if s + d > lo])
    return out


def _op_name(found, name):
    return found.get(scopes.instruction(name), "")


def _scope_ns(tr, steps, found, scope):
    per_step, seen = [], False
    for ops in _ops_by_step(tr, steps):
        mine = [(s, e) for name, s, e in ops
                if scope in _op_name(found, name).split("/")]
        seen = seen or bool(mine)
        per_step.append(sum(e - s for s, e in _union(mine)))
    return per_step if seen else None


def _unscoped_ns(tr, steps, found):
    left, seen = 0.0, False
    for (lo, hi), ops in zip(steps, _ops_by_step(tr, steps), strict=True):
        scoped = [(s, e) for name, s, e in ops if any(
            sc in _op_name(found, name).split("/") for sc in scopes.SCOPES)]
        seen = seen or bool(scoped)
        left += (hi - lo) - sum(e - s for s, e in _union(scoped))
    return left / len(steps) if seen else None


# ------------------------------------------------------ a synthetic trace
def synthetic(seed: int, n_steps: int = 1000, per_step: int = 200):
    """``(trace, hlo_text)``: ``n_steps`` steps of ``per_step`` ops each,
    the instruction ``fusion.<j>`` the ``j``-th op of every step, its
    ``op_name`` one of ``PATHS`` in turn. Ops start anywhere from a little
    before their step to its end and last from nothing to a tenth of it, so
    they overlap one another and some straddle a bound of the step; every
    fiftieth step starts where the one before ends, a few ops lie between
    steps, and the first before the window. Times are
    fractional ns, as the profiler gives them."""
    rng = np.random.default_rng(seed)
    length = rng.uniform(2e5, 4e5, n_steps)
    gap = rng.uniform(5e4, 3e5, n_steps)
    gap[1::50] = 0.0
    edges = 1e6 + np.cumsum(np.column_stack([gap, length]).ravel())
    lo, hi = edges[0::2], edges[1::2]
    start = lo[:, None] + length[:, None] * rng.uniform(
        -0.02, 1.0, (n_steps, per_step))
    dur = length[:, None] * np.where(
        rng.random((n_steps, per_step)) < 0.95,
        rng.exponential(0.004, (n_steps, per_step)),
        rng.uniform(0.0, 0.1, (n_steps, per_step)))
    dur[rng.random(dur.shape) < 0.01] = 0.0
    names = [f"%fusion.{j} = f32[8]{{0}} fusion()" for j in range(per_step)]
    ops = sorted(([names[j], float(s), float(d)]
                  for row_s, row_d in zip(start, dur, strict=True)
                  for j, (s, d) in enumerate(zip(row_s, row_d, strict=True))),
                 key=lambda ev: ev[1])
    between = [["%fusion.0 = f32[8]{0} fusion()", float(h + 1e3), 2e3]
               for h in hi[::7]]
    ops = sorted(ops + between + [["%copy.1 = f32[8]{0} copy()", 5e5, 6e5]],
                 key=lambda ev: ev[1])
    modules = [[f"jit_serve_step({k % 3})", float(a), float(b - a)]
               for k, (a, b) in enumerate(zip(lo, hi, strict=True))]
    modules += [["jit_convert(9)", float(h + 2e4), 1e3] for h in hi[::5]]
    spans = [["window", float(lo[0] - 3e5), float(hi[-1] + 3e5 - lo[0])]]
    for a, b in zip(lo, hi, strict=True):
        cut = np.sort(rng.uniform(b, b + 5e4, 2))
        spans += [["step", float(a - 2e3), float(b - a + 1e3)],
                  ["readback", float(b), float(cut[0] - b)],
                  ["form_batch", float(cut[1]), float(2e3)]]
    spans.sort(key=lambda ev: ev[1])
    body = "\n".join(
        f'  %fusion.{j} = f32[8]{{0}} fusion(), metadata={{op_name='
        f'"{PATHS[j % len(PATHS)]}"}}' for j in range(per_step))
    hlo = (f"HloModule jit_serve_step\n\nENTRY %main (p: f32[8]) -> f32[8] "
           f"{{\n{body}\n  %copy.1 = f32[8]{{0}} copy()\n}}\n")
    return {"ops": ops, "modules": modules, "spans": spans}, hlo


@functools.cache
def _case(name: str) -> tuple[dict, dict | None]:
    """``(trace, op_scopes)`` of a case; the small cut has no scopes."""
    if name == "synthetic":
        tr, hlo = synthetic(2 ** 31 + 17)
        return tr, scopes.op_scopes(hlo)
    if name == "scoped":
        return SCOPED["trace"], SCOPED["op_scopes"]
    return SMALL["trace"], None


def _same(got: list, want: list) -> None:
    """Rows of the same names, and numbers equal to rel 1e-12."""
    assert len(got) == len(want)
    for a, b in zip(got, want, strict=True):
        assert [x for x in a if isinstance(x, str)] \
            == [x for x in b if isinstance(x, str)]
        assert [x for x in a if not isinstance(x, str)] == pytest.approx(
            [x for x in b if not isinstance(x, str)], rel=1e-12)


@pytest.mark.parametrize("case", ["scoped", "small", "synthetic"])
def test_one_reduction_reads_what_a_pass_per_reading_read(case):
    tr, found = _case(case)
    lo, hi = trace.window(tr)
    ops = trace.Ops.of(tr["ops"])
    busy = trace.Busy.of(ops, lo, hi)
    assert busy.busy_ns == pytest.approx(_busy_ns(tr, lo, hi), rel=1e-12)
    _same(busy.idle_intervals(), _idle_intervals(tr, lo, hi))
    for n in (3, 10, 1000):
        _same(busy.top_ops(n), _top_ops(tr, lo, hi, n))
        _same(busy.idle_by_span(tr["spans"], n),
              _idle_by_span(tr, lo, hi, n))
    assert len(busy.idle_by_span(tr["spans"], 1000)) > 1
    if found is None:
        return
    steps = trace.steps(tr, lo, hi)
    split = scopes.split(ops.names, ops.clip(steps), steps, found)
    named = set(split.segment_ns)
    assert named >= set(scopes.SCOPES)
    for segment in named | {"no_such_scope"}:
        want = _scope_ns(tr, steps, found, segment)
        got = split.segment_ns.get(segment)
        assert (got is None) == (want is None), segment
        if want is not None:
            assert got.tolist() == pytest.approx(want, rel=1e-12), segment
    assert float(split.unscoped_ns.mean()) == pytest.approx(
        _unscoped_ns(tr, steps, found), rel=1e-12)


def test_the_synthetic_trace_has_what_it_is_for():
    tr, found = _case("synthetic")
    lo, hi = trace.window(tr)
    steps = trace.steps(tr, lo, hi)
    assert len(steps) == 1000
    head = [op for op in tr["ops"] if op[1] < steps[50][0]]
    straddle = [op for op in head if any(
        s < op[1] < e < op[1] + op[2] or op[1] < s < op[1] + op[2]
        for s, e in steps[:50])]
    assert len(straddle) > 50
    assert any(op[1] < lo < op[1] + op[2] for op in tr["ops"])
    assert sum(a[1] == b[0] for a, b in zip(steps, steps[1:])) == 20
    assert set(scopes.segments(found["fusion.3"])) \
        == {"jit(serve_step)", "interact", "cross", "dot_general"}


def _traced_run(tr: dict, hlo: str, monkeypatch) -> harness.Run:
    """A ``Run`` of the trace ``tr``, one dispatch of four rows per step,
    whose compiled step is ``hlo``."""
    monkeypatch.setattr(scopes, "step_hlo", lambda cfg, shape: hlo)
    n = len(trace.steps(tr, *trace.window(tr)))
    rng = np.random.default_rng(3)
    pool = rng.integers(0, 1000, size=(4 * n, CFG["n_tables"],
                                       CFG["lookups"]))
    return harness.Run(
        cfg=CFG, max_batch=CFG["max_batch"], pool_indices=pool,
        batches=[np.arange(4 * b, 4 * b + 4) for b in range(n)],
        waiting=np.ones(n, bool), latency_ms=np.linspace(1.0, 9.0, 4 * n),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
               "hbm_bytes": 16e9}, trace=tr)


def test_a_traced_run_walks_its_trace_once(monkeypatch):
    """Every reader of the benchmark and the result's busy time and
    breakdown read one array copy of the ops, one cut of them to the steps
    and one to the window, and one split by scope."""
    tr, hlo = synthetic(2 ** 31 + 18, n_steps=60, per_step=50)
    run = _traced_run(tr, hlo, monkeypatch)
    calls = collections.Counter()

    def counted(name, fn):
        def call(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return call

    monkeypatch.setattr(trace.Ops, "of", classmethod(counted(
        "Ops.of", trace.Ops.of.__func__)))
    monkeypatch.setattr(trace.Ops, "clip", counted("clip", trace.Ops.clip))
    monkeypatch.setattr(trace.Busy, "of", classmethod(counted(
        "Busy.of", trace.Busy.of.__func__)))
    monkeypatch.setattr(scopes, "split", counted("split", scopes.split))
    monkeypatch.setattr(scopes, "step_hlo",
                        counted("step_hlo", scopes.step_hlo))
    got = harness.read_traced(run, BENCH["per_layer"])
    assert set(got["metrics"]) == {m["name"] for m in BENCH["per_layer"]}
    assert got["device"]["busy_s"] > 0 and got["breakdown"]["device_ops"]
    assert calls == {"Ops.of": 1, "clip": 2, "Busy.of": 1, "split": 1,
                     "step_hlo": 1}
    # a reader made again reads the kept reduction
    again = harness.read_traced(run, BENCH["per_layer"])
    assert again == got and calls["clip"] == 2
    # a run of the same trace cut to fewer dispatches is reduced anew
    fewer = dataclasses.replace(run, batches=run.batches[:-1])
    harness.read_traced(fewer, BENCH["per_layer"])
    assert calls["Ops.of"] == 2
