"""What a configuration's file fixes that the benchmark reads for itself.

A file states each table's vocabulary (``n_rows``) and pooling
(``lookups``) either as one number, the same for every table, or as a list
of ``n_tables`` numbers; ``per_table`` reads both forms as one tuple, and
the two forms of the same sizes mean the same thing everywhere. It states
the table ``dtype`` and the name of its plain reference, a module
``references/<reference>.py`` beside ``chipbench/``, which holds the
model's mathematics and the work its step requires.

A request's indices are laid out by pooling: where every table has the
same pooling ``L`` they are ``(T, L)``, table ``t`` in row ``t``; otherwise
they are one row of ``sum(lookups)`` ids, table ``t`` in the columns
``columns(lookups)[t]``. Both read the same through ``by_table``.
"""

from __future__ import annotations

import functools
import importlib.util
import re
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(RuntimeError):
    """A run that cannot be made: no chip, a bad cell, a missing file."""


def per_table(cfg: dict, key: str) -> tuple[int, ...]:
    """``cfg[key]`` for each of the ``n_tables`` tables: one number stands
    for every table, a list gives each its own."""
    n_tables, value = cfg["n_tables"], cfg.get(key)
    values = value if isinstance(value, list) else [value] * n_tables
    if len(values) != n_tables or not all(
            type(v) is int and v > 0 for v in values):
        raise BenchError(f"{cfg['name']}: {key} must be a positive whole "
                         f"number or a list of {n_tables} of them, not "
                         f"{value!r}")
    return tuple(values)


def columns(lookups: tuple[int, ...]) -> list[slice]:
    """The columns of each table in a request's flattened indices."""
    ends = np.cumsum(lookups).tolist()
    return [slice(end - n, end) for n, end in zip(lookups, ends, strict=True)]


def index_shape(lookups: tuple[int, ...]) -> tuple[int, ...]:
    """The shape of one request's indices: ``(T, L)`` where every table
    pools ``L``, else ``(sum(lookups),)``."""
    if len(set(lookups)) == 1:
        return (len(lookups), lookups[0])
    return (sum(lookups),)


def by_table(indices: np.ndarray, lookups: tuple[int, ...]
             ) -> list[np.ndarray]:
    """Each table's ids ``(rows, lookups[t])`` of the requests ``indices``,
    in either layout."""
    flat = indices.reshape(indices.shape[0], -1)
    if flat.shape[1] != sum(lookups):
        raise ValueError(f"indices of shape {indices.shape} do not hold "
                         f"{sum(lookups)} lookups a request")
    return [flat[:, cols] for cols in columns(lookups)]


def table_dtype(cfg: dict) -> np.dtype:
    """The dtype the file states for the embedding tables."""
    import jax.numpy as jnp

    try:
        return jnp.dtype(cfg["dtype"])
    except (KeyError, TypeError):
        raise BenchError(f"{cfg['name']}: dtype {cfg.get('dtype')!r} is "
                         f"not a table dtype") from None


def reference(cfg: dict):
    """The module ``references/<cfg["reference"]>.py``: the configuration's
    plain reference and its work counts."""
    name = cfg.get("reference")
    path = BENCH_DIR / "references" / f"{name}.py"
    if not isinstance(name, str) or not _NAME.match(name) \
            or not path.is_file():
        raise BenchError(f"{cfg['name']}: no reference module {path}")
    return _load(str(path))


@functools.cache
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + Path(path).stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
