"""Arch registry: every assigned architecture is a selectable ArchBundle.

A bundle owns everything the launcher needs per (arch x shape) cell:

* ``init`` — full-size param init (dry-run uses ``jax.eval_shape`` on it, so
  no memory is allocated);
* ``steps[shape]`` — a ``StepDef`` whose ``make_fn(bundle, mesh,
  multi_pod)`` builds the cell's ``CellPlan``: the jit target (train_step
  / serve_step), its ShapeDtypeStruct args and PartitionSpecs;
* ``param_rules`` / ``opt_rules`` — path-substring -> PartitionSpec rules
  (distributed/shardings.py); opt rules default to param rules and may add
  ZeRO-style axes for optimizer state;
* ``model_flops[shape]`` — analytic MODEL_FLOPS for the §Roofline
  useful-compute ratio.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax


@dataclasses.dataclass
class StepDef:
    kind: str                                  # train | serve
    make_fn: Callable[[Any], Callable]         # bundle -> jit-target callable
    input_specs: Callable[[bool], tuple]       # multi_pod -> args (SDS trees)
    donate: tuple = ()                         # donated argnums
    static: tuple = ()                         # static argnums
    batch_arg_axes: dict | None = None         # overrides for batch sharding


@dataclasses.dataclass
class CellPlan:
    """What a step builder returns: the jit target, its ShapeDtypeStruct
    args, and the in/out PartitionSpec trees."""
    fn: Any
    args: tuple
    in_specs: tuple
    out_specs: Any
    donate: tuple = ()


def bt_axes(multi_pod: bool):
    """Mesh axes the batch dimension shards over."""
    return ("pod", "data") if multi_pod else ("data",)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@dataclasses.dataclass
class ArchBundle:
    name: str
    family: str                                # gnn | recsys
    cfg: Any
    init: Callable
    steps: dict[str, StepDef]
    param_rules: list
    opt_rules: list | None = None
    model_flops: dict[str, float] | None = None
    optimizer: Any = None                      # repro.optim.Optimizer
    notes: str = ""

    def rules_for_opt(self):
        return self.opt_rules if self.opt_rules is not None \
            else self.param_rules


_REGISTRY: dict[str, Callable[[], ArchBundle]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_arch(name: str) -> ArchBundle:
    import repro.configs.all_archs  # noqa: F401  (populates registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_archs() -> list[str]:
    import repro.configs.all_archs  # noqa: F401
    return sorted(_REGISTRY)


