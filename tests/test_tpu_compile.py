"""Compiles for a described TPU v5e (no chip attached) at the repo's widths.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached. That refuses what interpret mode accepts:
tiling-misaligned slices, scoped-VMEM overruns, programs that do not fit
device memory. A compile is not a run; nothing here executes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. All compiles happen in the test's own process.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import repro.models.dlrm as dlrm
from repro.configs.dlrm_rm2 import CONFIG as RM2
from repro.embedding.bag import pack_table
from repro.embedding.layout import PackedRanks
from repro.kernels.dot_interaction import dot_interaction
from repro.kernels.recflash_sls import recflash_sls
from repro.launch.serve import _store, serve_step

HBM_BYTES = 16e9            # one v5e chip
BATCH = 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


@pytest.mark.parametrize("dim,dtype", [(32, jnp.float32), (64, jnp.float32),
                                       (128, jnp.float32),
                                       (128, jnp.bfloat16)])
def test_recflash_sls_compiles(one_chip, dim, dtype):
    """The two-tier SLS at the repo's embedding widths, 1M-row table, the
    default 0.2% hot tier, 80 lookups."""
    n_rows, hot = 1_000_000, 2_000
    fn = jax.jit(lambda h, c, i: recflash_sls(h, c, i, interpret=False))
    text = fn.lower(_sds((hot, dim), dtype, one_chip),
                    _sds((n_rows - hot, dim), dtype, one_chip),
                    _sds((BATCH, 80), jnp.int32, one_chip)
                    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dim", [64, 128])
def test_dot_interaction_compiles(one_chip, dim):
    fn = jax.jit(lambda z: dot_interaction(z, interpret=False))
    text = fn.lower(_sds((BATCH, 27, dim), jnp.float32, one_chip)
                    ).compile().as_text()
    assert "tpu_custom_call" in text


def _rm2_shapes():
    """dlrm_rm2's parameters with logical (V, D) tables, its rank_of hash
    tables and a padded batch, as shapes."""
    cfg = RM2
    params = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), cfg))
    rank_ofs = [jax.ShapeDtypeStruct((n,), jnp.int32) for n in cfg.n_rows]
    batch = {"dense": jax.ShapeDtypeStruct((BATCH, cfg.n_dense),
                                           jnp.float32),
             "indices": jax.ShapeDtypeStruct(
                 (BATCH, cfg.n_tables, cfg.lookups), jnp.int32)}
    return cfg, params, rank_ofs, batch


def _served_step(one_chip, placed: bool):
    """The served step at dlrm_rm2 width, lowered on the tables and rank_of
    hash tables as ``serve.place_tables`` stores them; or the step as it was
    before they were packed, on logical (V, D) tables and one rank_of array
    per table."""
    cfg, params, rank_ofs, batch = _rm2_shapes()
    step = serve_step
    if placed:
        params = {**params, "tables": [jax.eval_shape(pack_table, t)
                                       for t in params["tables"]]}
        rows = max(cfg.n_rows)
        rank_ofs = PackedRanks(jax.ShapeDtypeStruct(
            (-(-cfg.n_tables * rows // 128), 128), jnp.int32),
            cfg.n_tables, rows)
    else:
        step = jax.jit(lambda p, r, b, cfg: dlrm.forward(
            dlrm.add_remap(p, r), b, cfg), static_argnames="cfg")
    return cfg, step.lower(_on(params, one_chip), _on(rank_ofs, one_chip),
                           _on(batch, one_chip), cfg=cfg)


def test_served_forward_fits_one_chip(one_chip):
    """The served step at dlrm_rm2 width (26 x 1M x 64 f32, 80 lookups), on
    the tables as they are placed, fits one chip, and its rank_of hash
    tables are arguments, not table-length constants."""
    cfg, lowered = _served_step(one_chip, placed=True)
    assert not [line for line in lowered.as_text().splitlines()
                if "dense<" in line and f"{cfg.n_rows[0]}x" in line]
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 26 * 1_000_000 * 64 * 4
    assert total < HBM_BYTES


_COPY = re.compile(r"= (\w+)\[([\d,]+)\]\S* copy(?:-start)?\(")
_OP = re.compile(r"^\s+(?:ROOT )?%\S+ = .*?\s([a-z][\w-]*)\(")
_NOT_RUN = ("parameter", "get-tuple-element", "tuple", "bitcast", "constant")
_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _table_copies(hlo_text: str, table_elems: int) -> list[str]:
    """The copy instructions whose result holds at least one table."""
    return [line for line in hlo_text.splitlines()
            if (m := _COPY.search(line))
            and np.prod([int(d) for d in m.group(2).split(",")])
            >= table_elems]


def _computations(hlo_text: str) -> dict[str, list[str]]:
    """Each computation's instruction lines, by name; the entry's under
    ``"ENTRY"``."""
    found: dict[str, list[str]] = {}
    lines: list[str] = []
    for line in hlo_text.splitlines():
        if m := _COMPUTATION.match(line):
            lines = found.setdefault("ENTRY" if m.group(1) else m.group(2), [])
        else:
            lines.append(line)
    return found


def _branches(hlo_text: str) -> list[list[str]]:
    """The branch computations of each ``conditional`` of the entry."""
    return [m.group(1).replace("%", "").split(", ")
            for line in _computations(hlo_text)["ENTRY"]
            if (m := _BRANCHES.search(line))]


def _device_ops(hlo_text: str) -> list[str]:
    """The opcodes of the instructions that run on the device in one step,
    one device-trace event each: the entry computation's, and those of the
    largest branch of each of its ``conditional``s."""
    comps = _computations(hlo_text)

    def ops(name: str) -> list[str]:
        return [m.group(1) for line in comps[name]
                if (m := _OP.match(line)) and m.group(1) not in _NOT_RUN]

    return ops("ENTRY") + [op for names in _branches(hlo_text)
                           for op in max(map(ops, names), key=len)]


@pytest.mark.parametrize("placed", [True, False])
def test_served_step_gathers_from_the_tables_in_place(one_chip, placed):
    """On lane-dense (500k, 128) tables the compiled step copies no table
    and needs under 128 MB of temporaries, of which 68 MB hold the rank_of
    lines its translation gathers. On (1M, 64) tables, in the TPU's default
    column-major layout, it copies every table to row-major before its
    gather, each step: that is why ``place_tables`` packs them.

    The placed step also runs at most 400 device ops, one profiler event
    each a step: a v5e profile of a 51-s window dropped its events past
    ~4.5M ops, at ~7,500 of ~10,700 steps of 599 ops (one rank_of array per
    table, each copied into fast memory every step). Its translation and
    SLS each run in one ``conditional`` with a branch per row bucket, 8,
    16, 32 and 64 rows, of which a step runs one."""
    cfg, lowered = _served_step(one_chip, placed)
    compiled = lowered.compile()
    text = compiled.as_text()
    copies = _table_copies(text, cfg.n_rows[0] * cfg.embed_dim)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if placed:
        assert copies == []
        assert temp < 128e6
        assert len(_device_ops(text)) <= 400
        assert [len(names) for names in _branches(text)] == [4, 4]
    else:
        assert len(copies) == cfg.n_tables
        assert temp > 2 * cfg.n_rows[0] * cfg.embed_dim * 4


@pytest.mark.parametrize("dim", [32, 64])
def test_table_placement_is_a_small_program(one_chip, dim):
    """``place_tables`` remaps and packs each 1M-row table in one program
    of under 1 MB of code, which the device holds beside the tables. (With
    the packing spelled as a plain reshape, the TPU compiler takes a minute
    or two and makes ~33 MB of code.)"""
    compiled = _store.lower(_sds((1_000_000, dim), jnp.float32, one_chip),
                            _sds((1_000_000,), jnp.int32, one_chip)
                            ).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 1e6


def test_row_sharded_forward_compiles_on_four_chips(topo):
    """The masked-psum SLS over tables row-sharded on a 1 x 4 mesh: a
    quarter of each table per device, bags reduced by an all-reduce."""
    cfg, params, rank_ofs, batch = _rm2_shapes()
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    replicated = NamedSharding(mesh, P())
    args = {"tables": _on(params["tables"],
                          NamedSharding(mesh, P("model", None))),
            "rank_of": _on(rank_ofs, NamedSharding(mesh, P("model"))),
            "bot": _on(params["bot"], replicated),
            "top": _on(params["top"], replicated)}
    compiled = jax.jit(lambda p, b: dlrm.forward(p, b, cfg, mesh=mesh)
                       ).lower(args, _on(batch, replicated)).compile()
    mem = compiled.memory_analysis()
    table_bytes = 26 * 1_000_000 * 64 * 4
    assert table_bytes / 4 < mem.argument_size_in_bytes < table_bytes / 2
    assert "all-reduce" in compiled.as_text()
