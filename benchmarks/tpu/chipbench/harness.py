"""Open-loop chip benchmark of the served DLRM path: set-up, window, checks.

One run serves one cell of ``BENCHMARK.json`` once. The cell names a
configuration file (``configs/<config>.json``) and a traffic file
(``traffic/<traffic>.json``); each per-layer metric is a reader in
``metrics/<name>.py``, and the configuration names its plain reference, a
module ``references/<reference>.py``. Nothing here names a cell, a
configuration, a model or a metric.

Set-up draws the request pool at the file's per-table sizes, then builds
what ``repro.launch.serve.main`` builds (``program.build``): the
deployment's offline phase (``Deployment(...).stats``, with no NAND lane),
a ``RemapSpec`` per table, ``serve.place_tables``, and ``serve.serve_step``
compiled once at the padded ``max_batch`` rows of the pool's index shape.
Then it runs a few warm-up steps.

The window offers the traffic's requests on the real clock. The program's
``DynamicBatcher.next_span`` decides each dispatch; the device is free again
when the previous step's logits are back on the host. Each request is timed
from its scheduled arrival to its logit on the host. The window closes to
arrivals after ``--seconds``; requests due in it are waited for, for at most
``DRAIN_S`` more, and one that never completes is failed.

After the window the program's state is freed and every served logit is
compared with the configuration's plain reference on the un-remapped
tables; ``correct`` needs every request served and the widest gap within the
configuration's limit.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections.abc import Callable
from pathlib import Path
from typing import Any

import numpy as np
from chipbench import config, program, trace, yardstick
from chipbench.config import BenchError
from chipbench.reference import NOT_A_NUMBER, logit_gap
from chipbench.traffic import Traffic, make_pool, make_schedule

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
SPANS = (trace.WINDOW_SPAN, "form_batch", "wait_arrival", "assemble",
         "transfer", "step", "readback")
WARMUP_STEPS = 3
DRAIN_S = 60.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def process_age_s() -> float:
    """Seconds since this process started."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# --------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict                   # the configuration's file
    traffic: Traffic
    end_to_end: list[dict]      # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {bench_path}; have "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = Traffic.load(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), cfg=cfg, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def load_reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise BenchError(f"no reader for metric {metric!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def weight_seed(seed: int) -> int:
    """The 31-bit seed of the weights: ``jax.random.PRNGKey`` keeps only
    the low 32 bits of a larger seed, so draw one from the whole seed."""
    return int(np.random.default_rng([seed, 2]).integers(2 ** 31))


def tpu_device(chips: int):
    """The first device, which must be a TPU, with ``chips`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX's first device is on "
                         f"platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[0]


# ---------------------------------------------------------------- records
@dataclasses.dataclass
class Run:
    """What one run recorded; the metric readers read it."""

    cfg: dict
    max_batch: int
    pool_indices: np.ndarray
    batches: list[np.ndarray]   # pool ids of each dispatch in the window
    waiting: np.ndarray         # dispatch went when the device freed
    latency_ms: np.ndarray      # scheduled arrival to logit, served requests
    peaks: dict | None = None
    trace: dict | None = None   # chipbench.trace.extract(...) or None

    @property
    def fills(self) -> np.ndarray:
        return np.array([b.size for b in self.batches], np.float64)

    def kept(self, name: str, make: Callable[[], Any]) -> Any:
        """``make()``, made once for the readers and kept while ``trace``
        and ``batches`` stay the same."""
        memo = self.__dict__.get("_kept")
        if memo is None or memo[0] is not self.trace \
                or memo[1] != len(self.batches):
            memo = self._kept = (self.trace, len(self.batches), {})
        if name not in memo[2]:
            memo[2][name] = make()
        return memo[2][name]

    def ops(self) -> trace.Ops:
        """The trace's device ops, read into arrays once."""
        return self.kept("ops", lambda: trace.Ops.of(self.trace["ops"]))

    def busy(self) -> trace.Busy | None:
        """The device ops of the traced window (``window``), reduced once;
        ``None`` without a window."""
        win = self.window()
        return None if win is None else self.kept(
            "busy", lambda: trace.Busy.of(self.ops(), *win))

    def _traced(self) -> tuple[tuple[float, float] | None, list | None]:
        """The traced window and the device ``(start, end)`` of the step of
        each dispatch in it, in ns.

        Where the trace holds one step execution per dispatch, that is the
        harness's whole window and every dispatch. The profiler keeps a
        fixed number of device events and drops those after it, so a window
        of more dispatches holds the steps of its first ones only: then the
        traced window ends with the last step whose every op was kept, and
        the steps are those of the first dispatches. No steps where the
        trace holds more step executions than dispatches, or none."""
        return self.kept("traced", self._cut)

    def _cut(self) -> tuple[tuple[float, float] | None, list | None]:
        win = None if self.trace is None else trace.window(self.trace)
        if win is None:
            return None, None
        found = trace.steps(self.trace, *win)
        if len(found) == len(self.batches):
            return win, found
        ends = self.ops().end
        last_op = ends.max() if ends.size else 0
        kept = [step for step in found if step[1] <= last_op]
        if not kept or len(found) > len(self.batches):
            return win, None
        return (win[0], kept[-1][1]), kept

    def window(self) -> tuple[float, float] | None:
        return self._traced()[0]

    def steps(self) -> list[tuple[float, float]] | None:
        """Device ``(start, end)`` of the step of each dispatch in the traced
        window, in ns: of every dispatch, or of the first ones (``_traced``).
        A reader that pairs steps with dispatches takes the first
        ``len(steps)`` of ``batches``."""
        return self._traced()[1]


class _CompileCounter:
    """Counts traces and compiles while armed."""

    def __init__(self) -> None:
        self.armed = False
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if left > 2e-3:
            time.sleep(left - 1e-3)


@contextlib.contextmanager
def _no_span(_name: str):
    yield


def read_traced(run: Run, per_layer: list[dict],
                lap: Callable[[str], None] = lambda _name: None) -> dict:
    """What a traced run's result takes from its trace: the per-layer
    metrics (``metrics``), the device's busy time and the traced window's
    length (``device``) and the ``breakdown``; the last two where the trace
    holds the window. ``lap(name)`` is called after each reader and after
    the rest."""
    out: dict = {"metrics": {}}
    for m in per_layer:
        value = load_reader(m["name"])(run)
        if value is not None:
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        lap("read " + m["name"])
    busy = run.busy()
    if busy is not None:
        out["device"] = {"busy_s": busy.busy_ns * 1e-9,
                         "window_s": (busy.hi - busy.lo) * 1e-9}
        out["breakdown"] = {
            "device_ops": busy.top_ops(),
            "idle_gaps": busy.idle_by_span(run.trace["spans"])}
    lap("busy_and_breakdown")
    return out


# ------------------------------------------------------------------ a run
def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             require_tpu: bool = True, log=print) -> dict:
    """Run ``cell`` once; return the result line's object."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import BatcherConfig, DynamicBatcher

    parts: dict[str, float] = {"start": process_age_s()}
    mark = time.perf_counter()

    def lap(name: str, into: dict[str, float] = parts) -> None:
        nonlocal mark
        now = time.perf_counter()
        into[name] = now - mark
        mark = now

    cfg, traffic = cell.cfg, cell.traffic
    reference = config.reference(cfg)
    dev = tpu_device(cell.chips) if require_tpu else jax.devices()[0]
    peaks = yardstick.load_peaks(BENCH_DIR / "peaks.json", dev.device_kind) \
        if require_tpu else None
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    lap("import")

    pool_idx, pool_dense = make_pool(
        traffic, config.per_table(cfg, "n_rows"),
        config.per_table(cfg, "lookups"), cfg["n_dense"], seed)
    arrival_us, pool_ids = make_schedule(traffic, seconds, seed)
    lap("pool")

    # --- the deployment, as serve.main builds it, with no NAND lane ------
    max_batch, max_wait_us = cfg["max_batch"], cfg["max_wait_us"]
    wseed = weight_seed(seed)
    prog = program.build(cfg, pool_idx.shape[1:], wseed, lap=lap)
    step, params, rank_ofs = prog.step, prog.params, prog.ranks
    del prog

    for w in range(WARMUP_STEPS):
        ids = np.arange(w * max_batch, (w + 1) * max_batch) % traffic.pool
        batch = {"dense": jnp.asarray(pool_dense[ids], jnp.float32),
                 "indices": jnp.asarray(pool_idx[ids], jnp.int32)}
        np.asarray(jax.block_until_ready(step(params, rank_ofs, batch)))
    lap("warmup")

    # --- the window ---------------------------------------------------------
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    span = TraceAnnotation if traced else _no_span
    batcher = DynamicBatcher(BatcherConfig(max_batch=max_batch,
                                           max_wait_us=max_wait_us))
    n = arrival_us.size
    done_us = np.full(n, np.nan)
    served = np.full(n, np.nan, np.float32)
    batches: list[np.ndarray] = []
    waiting: list[bool] = []
    dispatch_lag_us: list[float] = []
    # (start, assemble and transfer, step, readback) of each dispatch, in s
    cycles: list[tuple[float, float, float, float]] = []
    pos, free_us, batch, out = 0, 0.0, None, None
    close_us = (seconds + DRAIN_S) * 1e6
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans only, not every call
        jax.profiler.start_trace(logdir, profiler_options=options)
    setup_s = process_age_s()
    counter.armed = True
    t0 = time.perf_counter()
    with span(trace.WINDOW_SPAN):
        while pos < n:
            with span("form_batch"):
                end, dispatch_us = batcher.next_span(arrival_us, pos, free_us)
            with span("wait_arrival"):
                _sleep_until(t0 + dispatch_us * 1e-6)
            now_us = (time.perf_counter() - t0) * 1e6
            if now_us > close_us:
                break
            dispatch_lag_us.append(now_us - dispatch_us)
            c0 = time.perf_counter()
            with span("assemble"):
                ids = pool_ids[pos:end]
                dense = serve._pad(pool_dense[ids], max_batch)
                idx = serve._pad(pool_idx[ids], max_batch)
            with span("transfer"):
                batch = {"dense": jnp.asarray(dense, jnp.float32),
                         "indices": jnp.asarray(idx, jnp.int32)}
            c1 = time.perf_counter()
            with span("step"):
                out = jax.block_until_ready(step(params, rank_ofs, batch))
            c2 = time.perf_counter()
            with span("readback"):
                logits = np.asarray(out)
            c3 = time.perf_counter()
            cycles.append((c0 - t0, c1 - c0, c2 - c1, c3 - c2))
            waiting.append(dispatch_us == free_us)
            free_us = (time.perf_counter() - t0) * 1e6
            done_us[pos:end] = free_us
            served[pos:end] = logits[:end - pos]
            batches.append(ids)
            pos = end
    counter.armed = False
    timed: dict[str, float] = {}    # seconds of the window and what follows
    mark = t0
    lap("window", timed)
    if traced:
        jax.profiler.stop_trace()
        lap("stop_trace", timed)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    del step, params, rank_ofs, batch, out
    gc.collect()

    # --- what the requests saw ----------------------------------------------
    ok = np.isfinite(done_us)
    n_done = int(ok.sum())
    wait_ms = (done_us[ok] - arrival_us[ok]) * 1e-3
    last_done_s = float(done_us[ok].max()) * 1e-6 if n_done else None
    e2e = {"p50_ms": float(np.percentile(wait_ms, 50)) if n_done else None,
           "served_rps": n_done / last_done_s if n_done else None,
           "setup_s": setup_s}

    # --- correctness: every served logit against the reference ------------
    used = np.unique(pool_ids[ok])
    want = np.full(traffic.pool, np.nan, np.float32)
    mark = time.perf_counter()
    want[used] = reference.logits(cfg, wseed, pool_idx[used],
                                  pool_dense[used])
    lap("reference", timed)
    gap = logit_gap(served[ok], want[pool_ids[ok]]) \
        if n_done else NOT_A_NUMBER
    checks = {"logit_gap": {"value": gap, "limit": cfg["logit_gap_limit"]},
              "unserved": {"value": n - n_done, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    run = Run(cfg=cfg, max_batch=max_batch, pool_indices=pool_idx,
              batches=batches, waiting=np.array(waiting, bool),
              latency_ms=wait_ms, peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result: dict = {"correct": bool(correct), "attempted": int(n),
                    "failed": int(n - n_done)}
    if traced:
        mark = time.perf_counter()
        run.trace = trace.extract(logdir, SPANS)
        shutil.rmtree(logdir, ignore_errors=True)
        lap("extract", timed)
        got = read_traced(run, cell.per_layer,
                          lambda name: lap(name, timed))
        result["metrics"] = got["metrics"]
        device.update(got.get("device", {}))
        if "breakdown" in got:
            result["breakdown"] = got["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    result["device"] = device
    dispatch_lag = np.array(dispatch_lag_us or [0.0])
    result["notes"] = {
        "setup_parts_s": parts, "run_parts_s": timed,
        "compile_cache": cache_dir,
        "compiles_in_window": counter.count, "dispatches": len(batches),
        "dispatch_late_us_p50_p99": [
            float(np.percentile(dispatch_lag, 50)),
            float(np.percentile(dispatch_lag, 99))],
        "window_s": seconds, "last_completion_s": last_done_s,
        "slowest_dispatches_start_s_prep_step_read_ms": [
            [c[0]] + [x * 1e3 for x in c[1:]]
            for c in sorted(cycles, key=lambda c: -sum(c[1:]))[:8]]}
    result["checks"] = checks
    log(f"[{cell.name}] seed {seed}: {n_done} of {n} served in "
        f"{len(batches)} dispatches; set-up {setup_s:.3f} s "
        f"{ {k: round(v, 3) for k, v in parts.items()} }; compiles in "
        f"window {counter.count}; dispatch late p50/p99 "
        f"{result['notes']['dispatch_late_us_p50_p99']} us", file=sys.stderr)
    return result


# ------------------------------------------------------------------ the CLI
def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"benchmarks/tpu/run.py: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
