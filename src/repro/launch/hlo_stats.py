"""Trip-count-aware analysis of compiled HLO text (§Roofline input).

``compiled.cost_analysis()`` visits a ``while`` body **once** — verified
empirically: a matmul scanned 10x reports the same flops as a single matmul
— so scanned-layer models are undercounted ~n_layers-fold, and it does
not expose collective bytes at all. This module parses the
optimized HLO instead and attributes everything through the call graph:

* **Loop trip counts** — a collective/dot/byte inside a ``while`` body
  executes ``trip`` times; the trip count is recovered from the loop-
  condition computation's comparison constant (the standard XLA counted-loop
  shape emitted by ``lax.scan``/``fori_loop``).
* **FLOPs** — 2 x prod(result dims) x prod(contracting dims) per ``dot``,
  from the per-computation symbol table (operand shapes).
* **Memory bytes** — output + operand bytes per instruction, skipping
  zero-cost ops (parameter/tuple/gte/bitcast/constant). Computations reached
  through ``calls=``/``to_apply=`` (fusion bodies, reducers) contribute
  FLOPs only — their internal traffic stays in registers; the fusion's
  operands/outputs are counted at the call site.
* **Wire volume** — per-type ring factors convert buffer sizes to link
  traffic:

    all-reduce         2 x size x (n-1)/n
    all-gather         size x (n-1)/n          (size = full result)
    reduce-scatter     operand x (n-1)/n
    all-to-all         size x (n-1)/n
    collective-permute size

  ``n`` (participants) comes from replica_groups when present.
"""

from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*(\d+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_OP_SPLIT_RE = re.compile(r"^(.*?)\s*\b([a-z][a-z0-9\-]*)\(")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")

# ops whose "bytes" are free (aliasing / metadata only)
_BYTES_SKIP = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "add-dependency", "iota", "while",
               "conditional"}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> list[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        g = m.group(1).strip()
        return len(g.split(",")) if g else default
    return default


@dataclasses.dataclass
class _CompStats:
    counts: dict
    result_bytes: dict
    wire_bytes: dict
    whiles: list          # (cond_name, body_name)
    calls: list           # called computation names (fusion/to_apply)
    flops: float = 0.0
    mem_bytes: float = 0.0
    upcast_bytes: float = 0.0  # f32 results of bf16->f32 converts (CPU-only
    #                            artifact: XLA CPU upconverts bf16 dots; the
    #                            hoisted copies inflate memory_analysis)
    promoted_wire: float = 0.0  # wire of f32-"promoted" reductions (CPU-only:
    #                             XLA CPU promotes bf16 all-reduces to f32 —
    #                             reducer named "..._promoted"; on TPU these
    #                             collectives run in bf16 at half the bytes)
    max_const: int = 1    # max integer constant (trip-count heuristic)


def _new_stats() -> _CompStats:
    return _CompStats({c: 0 for c in _COLLECTIVES},
                      {c: 0 for c in _COLLECTIVES},
                      {c: 0.0 for c in _COLLECTIVES}, [], [])


def _parse_computations(hlo_text: str) -> tuple[dict, str | None]:
    comps: dict[str, _CompStats] = {}
    symbols: dict[str, str] = {}
    cur: _CompStats | None = None
    entry_name = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        # computation header: "<name> (params...) -> <shape> {"
        # (no "=" before the first paren distinguishes it from instructions)
        if line.endswith("{") and "->" in line \
                and "=" not in line.split("(", 1)[0]:
            name = line.split("(", 1)[0].replace("ENTRY", "").strip() \
                .lstrip("%")
            if name:
                cur = _new_stats()
                comps[name] = cur
                symbols = {}
                if raw.startswith("ENTRY"):
                    entry_name = name
                continue
        if cur is None or "=" not in line:
            continue
        lhs, rhs = line.split("=", 1)
        lhs = lhs.replace("ROOT", "").strip().lstrip("%")
        rhs = rhs.strip()
        m = _OP_SPLIT_RE.match(rhs)
        if not m:
            continue
        shape_part, op = m.group(1), m.group(2)
        symbols[lhs] = shape_part
        if op == "while":
            cm, bm = _COND_RE.search(rhs), _BODY_RE.search(rhs)
            tm = _TRIP_RE.search(rhs)           # XLA known_trip_count
            if cm and bm:
                cur.whiles.append((cm.group(1), bm.group(1),
                                   int(tm.group(1)) if tm else None))
            continue
        for c in _CONST_RE.finditer(line):
            cur.max_const = max(cur.max_const, int(c.group(1)))
        # operand names (first paren group; operand lists never nest parens)
        args_end = rhs.find(")", m.end())
        args_part = rhs[m.end():args_end if args_end >= 0 else len(rhs)]
        operands = _OPERAND_RE.findall(args_part)

        if op in ("dot", "dot-general"):
            cdims = _CONTRACT_RE.search(rhs)
            k = 1
            if cdims and operands:
                lhs_dims = _shape_dims(symbols.get(operands[0], ""))
                for d in (cdims.group(1).split(",")
                          if cdims.group(1) else []):
                    di = int(d)
                    if di < len(lhs_dims):
                        k *= lhs_dims[di]
            out_n = 1
            for d in _shape_dims(shape_part):
                out_n *= d
            cur.flops += 2.0 * out_n * k
        if op not in _BYTES_SKIP:
            nbytes = _shape_bytes(shape_part)
            for o in operands:
                nbytes += _shape_bytes(symbols.get(o, ""))
            cur.mem_bytes += nbytes
        if op in ("convert", "fusion") and "f32[" in shape_part and operands:
            src_shape = symbols.get(operands[0], "")
            if "bf16[" in src_shape and "convert" in rhs:
                cur.upcast_bytes += _shape_bytes(shape_part)

        coll = None
        for c in _COLLECTIVES:
            if op == c or op == c + "-start":
                coll = c
                break
        if coll is None:
            cm = _CALL_RE.search(rhs)
            if cm:
                cur.calls.append(cm.group(1))
            continue
        size = _shape_bytes(shape_part)
        cur.counts[coll] += 1
        cur.result_bytes[coll] += size
        n = max(2, _group_size(rhs, 0) or 2)
        ring = (n - 1) / n
        if coll == "all-reduce":
            wire = 2.0 * size * ring
        elif coll == "reduce-scatter":
            wire = size * n * ring
        elif coll == "collective-permute":
            wire = float(size)
        else:
            wire = size * ring
        cur.wire_bytes[coll] += wire
        if "_promoted" in rhs and "f32[" in shape_part:
            cur.promoted_wire += wire
    return comps, entry_name


@dataclasses.dataclass
class _Agg:
    coll: dict            # collective -> (count, result_bytes, wire_bytes)
    flops: float = 0.0
    mem_bytes: float = 0.0
    promoted_wire: float = 0.0


def _zero_agg() -> _Agg:
    return _Agg({c: (0, 0, 0.0) for c in _COLLECTIVES})


def _accumulate(comps: dict, name: str, seen: frozenset,
                flops_only: bool = False) -> _Agg:
    """Effective stats of computation ``name`` incl. loops and calls."""
    if name not in comps or name in seen:
        return _zero_agg()
    seen = seen | {name}
    cs = comps[name]
    out = _Agg({c: (cs.counts[c], cs.result_bytes[c], cs.wire_bytes[c])
                for c in _COLLECTIVES}, flops=cs.flops,
               mem_bytes=0.0 if flops_only else cs.mem_bytes,
               promoted_wire=0.0 if flops_only else cs.promoted_wire)
    if flops_only:
        out.coll = {c: (0, 0, 0.0) for c in _COLLECTIVES}

    def add(dst: _Agg, src: _Agg, mult: float = 1.0) -> _Agg:
        return _Agg({c: (dst.coll[c][0] + src.coll[c][0] * mult,
                         dst.coll[c][1] + src.coll[c][1] * mult,
                         dst.coll[c][2] + src.coll[c][2] * mult)
                     for c in _COLLECTIVES},
                    flops=dst.flops + src.flops * mult,
                    mem_bytes=dst.mem_bytes + src.mem_bytes * mult,
                    promoted_wire=dst.promoted_wire
                    + src.promoted_wire * mult)

    for callee in cs.calls:
        # fusion bodies / reducers: internal traffic stays on-chip
        out = add(out, _accumulate(comps, callee, seen, flops_only=True))
    for cond, body, trip in cs.whiles:
        if trip is None:     # no known_trip_count: cond-constant heuristic
            trip = comps[cond].max_const if cond in comps else 1
        trip = max(1, trip)
        out = add(out, _accumulate(comps, body, seen, flops_only=flops_only),
                  mult=trip)
    return out


def hlo_stats(hlo_text: str, mesh_size: int) -> dict:
    """Trip-count-corrected {collectives, flops, bytes} for the entry."""
    comps, entry = _parse_computations(hlo_text)
    if entry is None:
        entry = next(iter(comps)) if comps else None
    if entry is None:
        zero = {"count": 0, "result_bytes": 0, "wire_bytes": 0.0}
        return {"per_op": {c: dict(zero) for c in _COLLECTIVES},
                "total": dict(zero), "flops": 0.0, "bytes": 0.0}
    eff = _accumulate(comps, entry, frozenset())
    per_op = {c: {"count": eff.coll[c][0], "result_bytes": eff.coll[c][1],
                  "wire_bytes": eff.coll[c][2]} for c in _COLLECTIVES}
    total = {k: sum(v[k] for v in per_op.values())
             for k in ("count", "result_bytes", "wire_bytes")}
    return {"per_op": per_op, "total": total,
            "flops": eff.flops, "bytes": eff.mem_bytes,
            "entry_upcast_bytes": comps[entry].upcast_bytes,
            "promoted_wire_bytes": eff.promoted_wire}


def collective_stats(hlo_text: str, mesh_size: int) -> dict:
    """Back-compat wrapper: collectives only."""
    s = hlo_stats(hlo_text, mesh_size)
    return {"per_op": s["per_op"], "total": s["total"]}
