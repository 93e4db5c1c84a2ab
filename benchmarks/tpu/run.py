"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/tpu/run.py --workload rm2.hot.rate --seed 7 \
        --seconds 20 --trace 0

The cells, their configurations, traffic and metrics are named in
``BENCHMARK.json`` at the root of the checkout. The run needs a TPU and
exits nonzero without one, or without as many chips as the cell asks for.
The last line of standard output is one JSON object; the numbers that
decide ``correct`` are the last lines of standard error.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
