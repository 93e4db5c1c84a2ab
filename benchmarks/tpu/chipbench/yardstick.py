"""The work a served DLRM step requires, and the chip's peaks.

Kept with the benchmark, so that a change to the program cannot move the
yardstick. The operation count is a copy of
``repro.models.dlrm.DLRMConfig.flops_per_sample``, computed from the sizes
in the configuration's file. The byte count is what any implementation of
the step has to move between HBM and the core, once per step:

* each distinct embedding row that the batch touches, once (nothing stays
  in VMEM from one step to the next), at ``embed_dim`` x 4 B;
* the MLP weights and biases;
* the dense features and indices of the real rows in, their logits out.

It leaves out the ``rank_of`` reads (a layout choice, not work the model
requires) and the padded rows. So no implementation can read above 100%.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

F32 = 4


def top_in(cfg: dict) -> int:
    n = cfg["n_tables"] + 1
    if cfg["interaction"] == "dot":
        return cfg["embed_dim"] + n * (n - 1) // 2
    return n * cfg["embed_dim"]


def mlp_sizes(cfg: dict) -> tuple[tuple, tuple]:
    """Layer widths of the bottom and the top MLP, inputs first."""
    bot = (cfg["n_dense"],) + tuple(cfg["bot_mlp"])
    if bot[-1] != cfg["embed_dim"]:
        bot = bot + (cfg["embed_dim"],)
    return bot, (top_in(cfg),) + tuple(cfg["top_mlp"]) + (1,)


def flops_per_sample(cfg: dict) -> int:
    """Forward FLOPs one request requires: 2 x MACs of the MLP layers, 2 x
    ``embed_dim`` for each pair ``i < j`` of the dot interaction, and one
    add per pooled element of the SLS.

    The arithmetic of ``DLRMConfig.flops_per_sample``, less three counts
    that are not required work: a bottom layer ``embed_dim -> embed_dim``
    that the model does not have, the lower triangle and diagonal of the
    interaction, and a multiply per pooled element.
    """
    bot, top = mlp_sizes(cfg)
    f = sum(2 * a * b for sizes in (bot, top)
            for a, b in zip(sizes[:-1], sizes[1:], strict=True))
    if cfg["interaction"] == "dot":
        n = cfg["n_tables"] + 1
        f += n * (n - 1) * cfg["embed_dim"]
    f += cfg["n_tables"] * cfg["lookups"] * cfg["embed_dim"]
    return f


def mlp_weight_bytes(cfg: dict) -> int:
    return sum(F32 * (a * b + b) for sizes in mlp_sizes(cfg)
               for a, b in zip(sizes[:-1], sizes[1:], strict=True))


def distinct_rows(indices: np.ndarray) -> int:
    """Distinct rows a batch touches, summed over tables.
    ``indices`` is ``(rows, n_tables, lookups)``."""
    return sum(int(np.unique(indices[:, t]).size)
               for t in range(indices.shape[1]))


def step_bytes(cfg: dict, indices: np.ndarray) -> int:
    """Bytes one step must move for the real rows ``indices``."""
    n = indices.shape[0]
    return (distinct_rows(indices) * cfg["embed_dim"] * F32
            + mlp_weight_bytes(cfg)
            + n * cfg["n_dense"] * F32
            + indices.size * F32
            + n * F32)


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
