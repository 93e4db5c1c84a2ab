"""Readings that set a cell's rate and the limits of its checks, on the chip.

    python3 benchmarks/tpu/calibrate.py --workload rm2.hot.rate \
        --capacity-seconds 10 --trace-seed 5 --seeds 11,12,13 \
        --control-seeds 21,22,23 --seconds 20 --out chiprun_out/cal.jsonl

Everything runs in one process, one run after the other, so the set-up that
runs share is paid once per process and not once per reading:

1. capacity (``--capacity-seconds``): the cell's traffic with every request
   due at the start of the window (MLPerf's "Offline" scenario), so the
   backlog never empties and ``served_rps`` is what the configuration
   sustains;
2. one traced run at the offered rate (``--trace-seed``), with the names of
   the trace's planes and lines and the number of events on each;
3. sound runs of the program on ``--seeds``;
4. the control on ``--control-seeds``: the program's own step compiled at the
   matmul precision below the configuration's (``high``, three bfloat16
   passes, for float32 at ``highest``), which has to come out not correct.

The offered rate is ``--rate``, or ``RATE_SHARE`` of the capacity measured
in step 1, or the traffic file's. Each run's result is one JSON line in
``--out``, written as soon as the run ends. The benchmark's own runs
(``run.py``) never run any of this.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import harness, trace  # noqa: E402

RATE_SHARE = 0.8
# the matmul precision one step below a configuration's
LOWER_PRECISION = {"highest": "high", "high": "default"}


def _plane_names(logdir: str) -> dict:
    from jax.profiler import ProfileData

    path, = glob.glob(str(Path(logdir) / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    data = ProfileData.from_file(path)
    return {p.name: {line.name: sum(1 for _ in line.events)
                     for line in p.lines} for p in data.planes}


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--capacity-seconds", type=float, default=0.0)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--trace-seed", type=int, default=None)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    args.out.parent.mkdir(parents=True, exist_ok=True)
    names: dict = {}
    extract = trace.extract

    def extract_and_name(logdir, span_names):
        names.update(_plane_names(logdir))
        return extract(logdir, span_names)

    trace.extract = extract_and_name

    def record(kind: str, c: harness.Cell, seed: int, seconds: float,
               traced: bool = False) -> dict:
        result = harness.run_cell(c, seed, seconds, traced)
        line = {"kind": kind, "workload": c.name, "seed": seed,
                "seconds": seconds, "rate_rps": c.traffic.rate_rps,
                "matmul_precision": c.cfg["matmul_precision"], **result}
        if traced:
            line["planes"] = dict(names)
        with args.out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line[k] for k in ("kind", "seed", "correct",
                                               "metrics", "checks")}),
              flush=True)
        return result

    rate = args.rate or cell.traffic.rate_rps
    if args.capacity_seconds:
        offline = dataclasses.replace(
            cell, traffic=dataclasses.replace(cell.traffic,
                                              arrivals="offline"))
        cap = record("capacity", offline, 1, args.capacity_seconds)
        served = cap["metrics"].get("served_rps", {}).get("value")
        if served and not args.rate:
            rate = float(round(RATE_SHARE * served))
    at_rate = dataclasses.replace(
        cell, traffic=dataclasses.replace(cell.traffic, rate_rps=rate))
    if args.trace_seed is not None:
        record("traced", at_rate, args.trace_seed, args.seconds, traced=True)
    for seed in args.seeds:
        record("sound", at_rate, seed, args.seconds)
    lower = dataclasses.replace(at_rate, cfg=dict(
        at_rate.cfg,
        matmul_precision=LOWER_PRECISION[at_rate.cfg["matmul_precision"]]))
    for seed in args.control_seeds:
        record("control", lower, seed, args.seconds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
