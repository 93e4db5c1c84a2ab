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

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import repro.models.dlrm as dlrm
from repro.configs.dlrm_rm2 import CONFIG as RM2
from repro.kernels.dot_interaction import dot_interaction
from repro.kernels.recflash_sls import recflash_sls
from repro.launch.serve import serve_step

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
    cfg = RM2
    params = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), cfg))
    rank_ofs = [jax.ShapeDtypeStruct((n,), jnp.int32) for n in cfg.n_rows]
    batch = {"dense": jax.ShapeDtypeStruct((BATCH, cfg.n_dense),
                                           jnp.float32),
             "indices": jax.ShapeDtypeStruct(
                 (BATCH, cfg.n_tables, cfg.lookups), jnp.int32)}
    return cfg, params, rank_ofs, batch


def test_served_forward_fits_one_chip(one_chip):
    """The served step at dlrm_rm2 width (26 x 1M x 64 f32, 80 lookups)
    fits one chip, and its rank_of hash tables are arguments, not
    table-length constants."""
    cfg, params, rank_ofs, batch = _rm2_shapes()
    lowered = serve_step.lower(_on(params, one_chip), _on(rank_ofs, one_chip),
                               _on(batch, one_chip), cfg=cfg)
    assert not [line for line in lowered.as_text().splitlines()
                if "dense<" in line and f"{cfg.n_rows[0]}x" in line]
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 26 * 1_000_000 * 64 * 4
    assert total < HBM_BYTES


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
