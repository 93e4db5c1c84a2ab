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

The ops of the run's steps are cut to the steps once, and ``split`` takes
every reading from that cut at once: the time of each segment of the ops'
``op_name`` paths, so a scope nested in another (``interact/cross``) is
read by its own name and counts in the outer one too, and the time of no
scope. The run keeps both for the next reader.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
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


def segments(op_name: str) -> set[str]:
    """The segments of the path ``op_name``: each scope it lies in, at any
    depth."""
    return set(op_name.split("/")) - {""}


def in_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a segment of the path ``op_name``."""
    return scope in segments(op_name)


@dataclasses.dataclass(frozen=True)
class Split:
    """Each step's device time by scope, from one walk of the trace: for
    every segment of the ``op_name`` of some op of the steps, the time in
    each step in which an op with that segment ran (the union of their
    intervals, clipped to the step), and the time in each step in which no
    op of ``SCOPES`` ran, ``None`` when no op resolves to one."""

    segment_ns: dict[str, np.ndarray]
    unscoped_ns: np.ndarray | None


def split(names: list[str], pieces: trace.Pieces,
          steps: list[tuple[float, float]], scopes: dict[str, str]) -> Split:
    """The ``Split`` of the device ops ``pieces``, cut to ``steps``
    (``trace.Ops.clip``; their names index ``names``), each op name's
    ``op_name`` resolved through ``scopes`` once."""
    n = len(steps)
    named: dict[str, list[int]] = collections.defaultdict(list)
    for i in np.unique(pieces.name_id).tolist():
        for segment in segments(scopes.get(instruction(names[i]), "")):
            named[segment].append(i)

    def covered(ids: list[int]) -> np.ndarray:
        mine = np.zeros(len(names), bool)
        mine[ids] = True
        return trace.covered_ns(pieces[mine[pieces.name_id]], n)

    scoped = sorted({i for scope in SCOPES for i in named.get(scope, ())})
    step_ns = np.array([hi - lo for lo, hi in steps], np.float64)
    return Split({segment: covered(ids) for segment, ids in named.items()},
                 step_ns - covered(scoped) if scoped else None)


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
            names = run.ops().names
            ran = {instruction(names[i])
                   for i in np.unique(_step_pieces(run).name_id).tolist()}
            if not ran <= found.keys():
                found = None
        run.op_scopes = found
    return run.op_scopes


def _step_pieces(run) -> trace.Pieces:
    """The run's device ops cut to its steps, kept on the run."""
    return run.kept("step_pieces", lambda: run.ops().clip(run.steps()))


def split_of(run) -> Split | None:
    """The ``split`` of the run's steps, made once and kept on the run for
    the next reader; ``None`` where ``of(run)`` is."""
    scopes = of(run)
    if scopes is None:
        return None
    return run.kept("scope_split", lambda: split(
        run.ops().names, _step_pieces(run), run.steps(), scopes))


def _mean_ms(per_step: np.ndarray) -> float:
    return float(per_step.sum()) / per_step.size * 1e-6


def scope_ms(run, scope: str) -> float | None:
    """Mean device time per step of the ops in the named scope ``scope``,
    which may be any segment of their ``op_name``, nested at any depth: the
    union of their intervals, clipped to each step's execution, in ms.
    ``None`` when no op resolves to ``scope``, so a renamed or removed scope
    reads null, not 0."""
    found = split_of(run)
    if found is None or scope not in found.segment_ns:
        return None
    return _mean_ms(found.segment_ns[scope])


def unscoped_ms(run) -> float | None:
    """Mean time per step in which no op of ``SCOPES`` ran, in ms; ``None``
    when no op resolves to any scope."""
    found = split_of(run)
    if found is None or found.unscoped_ns is None:
        return None
    return _mean_ms(found.unscoped_ns)


# ------------------------------------------------------ the SLS's roofline
def sls_least_time_s(cfg: dict, indices: np.ndarray, peaks: dict) -> float:
    """The least time the chip could take for the SLS of the real rows
    ``indices``: the work the configuration's reference counts
    (``sls_work``: each distinct (table, row) read once, one add per pooled
    element) over the chip's peaks."""
    flops, n_bytes = config.reference(cfg).sls_work(cfg, indices)
    return yardstick.least_time_s(flops, n_bytes, peaks)[0]
