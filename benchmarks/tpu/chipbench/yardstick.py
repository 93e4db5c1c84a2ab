"""The chip's peaks, and the arithmetic of a least time, for any model.

The work a step requires is the model's own: each configuration's reference
module (``references/<name>.py``) counts it, beside the mathematics it
counts. Here are the peaks table, the least time a count allows, and the
count of distinct rows that every embedding model's byte count starts from.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
from chipbench.config import by_table


def distinct_rows(indices: np.ndarray, lookups: tuple[int, ...]) -> int:
    """Distinct rows a batch touches, summed over tables: each table's
    over its own columns of ``indices`` (``config.by_table``)."""
    return sum(int(np.unique(ids).size) for ids in by_table(indices, lookups))


def load_peaks(path: Path, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = json.loads(Path(path).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"have {sorted(table['devices'])}") from None


def least_time_s(flops: float, n_bytes: float, peaks: dict
                 ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
