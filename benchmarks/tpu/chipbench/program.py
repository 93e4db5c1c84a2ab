"""The program under test, built from a configuration's file.

``build`` is the one place where the benchmark makes the served program:
the deployment (``DeploymentConfig.from_arch`` with every key the file
gives), the remapped tables placed on the device (``serve.place_tables``),
and ``serve.serve_step`` compiled at the pool's index shape and the file's
matmul precision. The harness builds the timed program with it, and the
scope readers build the same step again for its HLO text.

Before anything is timed it refuses a file that states what the program
does not run: per-table sizes it cannot take, or widths, MLPs,
interaction, vocabularies, pooling or table dtype other than what it built
and placed. The error names each key. What the program can take, and what
it built, is read off the program: ``from_arch``'s signature and
annotations, the fields of its model configuration, the placed tables.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import typing
from collections import abc
from typing import Any

import numpy as np
from chipbench.config import BenchError, per_table, table_dtype

# bytes of one table row's int32 ``rank_of`` entry, placed beside the table
RANK_BYTES = 4
# keys that a file may state per table
PER_TABLE = ("n_rows", "lookups")


@dataclasses.dataclass
class Program:
    model: Any          # the program's model configuration
    params: Any         # its weights, the tables remapped and placed
    ranks: Any          # the ``rank_of`` hash tables
    step: Any           # ``serve_step``, compiled


def _per_table(n_tables: int, value) -> tuple:
    return tuple(value) if isinstance(value, (list, tuple)) \
        else (value,) * n_tables


def _takes_sequence(hint) -> bool:
    """Whether a parameter annotated ``hint`` takes a sequence."""
    return any((typing.get_origin(a) or a) in (list, tuple, abc.Sequence)
               for a in typing.get_args(hint) or (hint,))


def deployment(cfg: dict):
    """``(DeploymentConfig, model configuration)`` of the program for the
    file ``cfg``: ``DeploymentConfig.from_arch`` given every key of the file
    that it or ``DeploymentConfig`` takes, each per-table size as one number
    where every table has the same, else as a list where ``from_arch``'s
    annotation takes one. A ``BenchError`` names each key where the file
    states what the program cannot take, or where any field of the
    program's model configuration differs from the file's key of that
    name."""
    from repro.serving import BatcherConfig, DeploymentConfig, arch_model_config

    stated = {key: per_table(cfg, key) for key in PER_TABLE}
    hints = typing.get_type_hints(DeploymentConfig.from_arch)
    named = inspect.signature(DeploymentConfig.from_arch).parameters
    takes = {k for k, p in named.items() if p.kind in (
        p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)} \
        | {f.name for f in dataclasses.fields(DeploymentConfig)}
    given = {k: v for k, v in cfg.items()
             if k in takes - {"arch", "batcher", "policies", "tables"}}
    several = {}
    for key, values in stated.items():
        if len(set(values)) == 1:
            given[key] = values[0]
        elif _takes_sequence(hints.get(key)):
            given[key] = list(values)
        else:
            several[key] = cfg[key]
    if several:
        raise BenchError(f"{cfg['name']}: the program takes one number for "
                         f"every table, and the file states several: "
                         f"{several}")
    dep_cfg = DeploymentConfig.from_arch(
        cfg["arch"], policies=(), **given,
        batcher=BatcherConfig(max_batch=cfg["max_batch"],
                              max_wait_us=cfg["max_wait_us"]))
    model = arch_model_config(dep_cfg)
    differ = {}
    for f in dataclasses.fields(model):
        if f.name == "name" or f.name not in cfg:
            continue
        ran, want = getattr(model, f.name), cfg[f.name]
        if f.name in PER_TABLE:
            ran, want = _per_table(model.n_tables, ran), stated[f.name]
        elif isinstance(want, list) and isinstance(ran, (list, tuple)):
            ran, want = tuple(ran), tuple(want)
        if ran != want:
            differ[f.name] = {"file": want, "program": ran}
    if differ:
        raise BenchError(f"the program runs {cfg['arch']} with other sizes "
                         f"than {cfg['name']}'s file states: {differ}")
    return dep_cfg, model


def build(cfg: dict, index_shape: tuple, weight_seed: int, *,
          offline: bool = True, lap=lambda _name: None,
          compile_scope=contextlib.nullcontext) -> Program:
    """Build, place and compile the served program of the file ``cfg`` for
    requests of ``index_shape`` (the pool's shape past its first axis).

    ``offline`` runs the deployment's offline phase (``Deployment(...)
    .stats``) for the served remap; without it every table gets the remap
    of zero counts, which places the tables in the same layout, for a
    compile only. ``lap(name)`` is called after each part. The compile
    runs inside ``compile_scope()``."""
    import jax
    import jax.numpy as jnp

    from repro.embedding.layout import RemapSpec
    from repro.launch import serve
    from repro.serving import Deployment

    dtype = table_dtype(cfg)
    dep_cfg, model = deployment(cfg)
    if offline:
        specs = [RemapSpec.from_counts(s.counts)
                 for s in Deployment(dep_cfg).stats]
    else:
        specs = [RemapSpec.from_counts(np.zeros(n, np.int64))
                 for n in model.n_rows]
    lap("offline_phase")

    serve.check_fits(sum(n * (cfg["embed_dim"] * dtype.itemsize + RANK_BYTES)
                         for n in model.n_rows), jax.devices()[0])
    params, ranks = serve.place_tables(model, specs, weight_seed)
    jax.block_until_ready((params, ranks))
    del specs
    placed = {str(t.dtype) for t in jax.tree.leaves(params["tables"])}
    if placed != {dtype.name}:
        raise BenchError(f"{cfg['name']}: the program placed its tables as "
                         f"{sorted(placed)}, the file states dtype "
                         f"{dtype.name!r}")
    lap("tables")

    shape = {"dense": jax.ShapeDtypeStruct((cfg["max_batch"], model.n_dense),
                                           jnp.float32),
             "indices": jax.ShapeDtypeStruct(
                 (cfg["max_batch"],) + tuple(index_shape), jnp.int32)}
    with compile_scope(), jax.default_matmul_precision(
            cfg["matmul_precision"]):
        step = serve.serve_step.lower(params, ranks, shape,
                                      cfg=model).compile()
    lap("compile")
    return Program(model=model, params=params, ranks=ranks, step=step)
