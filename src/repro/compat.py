"""The one place this repo touches ``jax.experimental`` (repro-lint RL005).

Pallas still lives under ``jax.experimental``; re-exporting it here keeps
that import surface at one call site, so when it graduates (or the TPU
submodule moves again) only this file changes. ``make_mesh`` and
``shard_map`` pin the two defaults every mesh user in the repo relies on:
Auto mesh axes and unchecked replication.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas  # noqa: F401
from jax.experimental.pallas import tpu as pallas_tpu  # noqa: F401
from jax.sharding import AxisType


def make_mesh(shape, axes, axis_types=None):
    """``jax.make_mesh`` with Auto axis types unless ``axis_types`` says
    otherwise (a tuple matching ``axes``)."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=axis_types)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
