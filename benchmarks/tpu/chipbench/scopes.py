"""Device time of each named scope of the served step, from a traced run.

The program runs each layer of its step under one ``jax.named_scope``
(``translate``, ``sls``, ``interact``, ``mlp``), which lands in the
``op_name`` of each HLO instruction's metadata. A v5e trace names each device
op by its instruction's HLO text and carries no ``op_name``, so the map from
instruction to scope comes from the compiled step's own HLO text
(``op_scopes``).

The harness frees its compiled step before the readers run, so ``of(run)``
compiles the step once more, from the run's configuration file and at its
pool's index shape, through the harness's own ``program.build``, and keeps
the map on the run. One HLO module compiles to the same instructions under
the same names, so the map names the ops that ran; an op of the run's steps
that the map does not hold makes every reading null. A program without the
scopes reads null too: no op resolves to one.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import re

import numpy as np
from chipbench import config, program, trace, yardstick

SCOPES = ("translate", "sls", "interact", "mlp")

_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = (.*)$")
_OPERAND = re.compile(r"%([^\s,(){}=]+)")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` for every instruction of the HLO
    module ``hlo_text`` (a compiled executable's ``as_text()``), in every
    computation; instruction names are unique in a module.

    An ``op_name`` counts only as a path of the traced program (it holds a
    ``/``, as ``jit(serve_step)/sls/jit(_take)/gather``). One without, such
    as a parameter's name that the compiler copied onto the relayout copy of
    that parameter, or a name a compiler pass made up (``gather``), counts
    as none. An instruction with none takes the ``op_name`` of its first
    consumer that has one, after that consumer's own resolution; the text
    lists an instruction before its consumers. So the relayout copies of
    the embedding tables, whose consumer is the gather in ``sls``, count in
    ``sls``. An instruction with no such consumer maps to ``""``.
    """
    order: list[str] = []
    own: dict[str, str] = {}
    consumers: dict[str, list[str]] = collections.defaultdict(list)
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        op_name = found.group(1) if found else ""
        own[name] = op_name if "/" in op_name else ""
        order.append(name)
        for operand in dict.fromkeys(_OPERAND.findall(rest.split(
                ", metadata=", 1)[0])):
            if operand != name:
                consumers[operand].append(name)
    resolved: dict[str, str] = {}
    for name in reversed(order):
        resolved[name] = own[name] or next(
            (resolved[c] for c in consumers[name] if resolved.get(c)), "")
    return {name: resolved[name] for name in order}


def instruction(event_name: str) -> str:
    """The HLO instruction a device op event stands for: its name up to the
    first space or ``=``, without a leading ``%``."""
    return re.split(r"[\s=]", event_name.lstrip("%"), maxsplit=1)[0]


def in_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a segment of the path ``op_name``."""
    return scope in op_name.split("/")


def _ops_by_step(tr: dict, steps: list[tuple[float, float]]
                 ) -> list[list[tuple[str, float, float]]]:
    """For each step, the device ops that overlap it, clipped to it."""
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


def _resolver(scopes: dict[str, str]):
    cache: dict[str, str] = {}

    def op_name(event_name: str) -> str:
        if event_name not in cache:
            cache[event_name] = scopes.get(instruction(event_name), "")
        return cache[event_name]
    return op_name


def scope_ns(tr: dict, steps: list[tuple[float, float]],
             scopes: dict[str, str], scope: str) -> list[float] | None:
    """For each step, the time in which some device op of ``scope`` ran:
    the union of the intervals of the ops whose ``op_name`` has ``scope`` as
    a path segment, clipped to the step. ``None`` when no op of any step
    resolves to ``scope``."""
    op_name = _resolver(scopes)
    per_step, seen = [], False
    for ops in _ops_by_step(tr, steps):
        mine = [(s, e) for name, s, e in ops if in_scope(op_name(name), scope)]
        seen = seen or bool(mine)
        per_step.append(sum(e - s for s, e in trace.union(mine)))
    return per_step if seen else None


def unscoped_ns(tr: dict, steps: list[tuple[float, float]],
                scopes: dict[str, str]) -> float | None:
    """The mean time per step, in ns, in which no op of ``SCOPES`` ran: idle
    time inside the step and ops of no scope. ``None`` when no op of any
    step resolves to a scope."""
    op_name = _resolver(scopes)
    left, seen = 0.0, False
    for (lo, hi), ops in zip(steps, _ops_by_step(tr, steps), strict=True):
        scoped = [(s, e) for name, s, e in ops
                  if any(in_scope(op_name(name), sc) for sc in SCOPES)]
        seen = seen or bool(scoped)
        left += (hi - lo) - sum(e - s for s, e in trace.union(scoped))
    return left / len(steps) if seen else None


# ------------------------------------------------- the run's compiled step
@contextlib.contextmanager
def _fresh_compile():
    """A compile that no in-memory cache answers and whose persistent cache
    key holds the metadata (``step_hlo``)."""
    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.clear_caches()
    jax.config.update(key, True)
    try:
        yield
    finally:
        jax.config.update(key, was)


def step_hlo(cfg: dict, index_shape: tuple) -> str:
    """The optimised HLO text of the served step of the configuration file
    ``cfg`` for requests of ``index_shape``, compiled as the harness
    compiles it (``program.build``), on the default device.

    The tables are placed for their layout on the device, which decides
    the step's relayout copies; their values do not matter (the remap of
    zero counts, weight seed 0), and they are freed on return. The
    persistent compile cache keys on the module without its metadata by
    default, so the harness's executable may hold the op names of another
    program with the same ops that put it there first. So this compile
    starts from cleared in-memory caches, which would give that executable
    back, and keys the persistent cache on the metadata too."""
    return program.build(cfg, index_shape, 0, offline=False,
                         compile_scope=_fresh_compile).step.as_text()


def of(run) -> dict[str, str] | None:
    """``op_scopes`` of the run's compiled step, kept on the run as
    ``run.op_scopes`` for the next reader. ``None`` when the trace holds no
    steps of the window, or when an op of those steps is no instruction of
    the compiled step."""
    if not hasattr(run, "op_scopes"):
        steps = run.steps()
        found = None
        if steps:
            found = op_scopes(step_hlo(run.cfg, run.pool_indices.shape[1:]))
            ran = {instruction(name) for ops in _ops_by_step(run.trace, steps)
                   for name, _, _ in ops}
            if not ran <= found.keys():
                found = None
        run.op_scopes = found
    return run.op_scopes


def scope_ms(run, scope: str) -> float | None:
    """Mean device time per step of the ops in the named scope ``scope``:
    the union of their intervals, clipped to each step's execution, in ms.
    ``None`` when no op resolves to ``scope``, so a renamed or removed scope
    reads null, not 0."""
    scopes = of(run)
    if scopes is None:
        return None
    per_step = scope_ns(run.trace, run.steps(), scopes, scope)
    return None if per_step is None else sum(per_step) / len(per_step) * 1e-6


def unscoped_ms(run) -> float | None:
    """Mean time per step in which no op of ``SCOPES`` ran, in ms; ``None``
    when no op resolves to any scope."""
    scopes = of(run)
    if scopes is None:
        return None
    ns = unscoped_ns(run.trace, run.steps(), scopes)
    return None if ns is None else ns * 1e-6


# ------------------------------------------------------ the SLS's roofline
def sls_least_time_s(cfg: dict, indices: np.ndarray, peaks: dict) -> float:
    """The least time the chip could take for the SLS of the real rows
    ``indices``: the work the configuration's reference counts
    (``sls_work``: each distinct (table, row) read once, one add per pooled
    element) over the chip's peaks."""
    flops, n_bytes = config.reference(cfg).sls_work(cfg, indices)
    return yardstick.least_time_s(flops, n_bytes, peaks)[0]
