"""The named scopes of the DLRM step: each layer's ops carry its scope in
the compiled program's metadata, and the scopes add no op.

``translate`` (the ``rank_of`` gather), ``sls`` (the pooled lookup),
``interact`` and ``mlp`` name the layers of ``dlrm.forward`` and
``dlrm.retrieval_score``; a profile of the compiled program splits device
time by them.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import repro.models.dlrm as dlrm

SCOPES = ("translate", "sls", "interact", "mlp")
CFG = dlrm.DLRMConfig(name="small", n_tables=3, n_dense=13, embed_dim=16,
                      n_rows=(1000,) * 3, lookups=4, bot_mlp=(32, 16),
                      top_mlp=(32, 16))


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """The scoped and unscoped programs differ in metadata alone, which the
    persistent cache's key leaves out: where an earlier test of the same
    process turned the cache on (the benchmark's harness does), the
    unscoped compile would load the scoped program."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(batch: int = 8):
    params = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), CFG))
    rank_ofs = [jax.ShapeDtypeStruct((n,), jnp.int32) for n in CFG.n_rows]
    data = {"dense": jax.ShapeDtypeStruct((batch, CFG.n_dense), jnp.float32),
            "indices": jax.ShapeDtypeStruct((batch, CFG.n_tables,
                                             CFG.lookups), jnp.int32)}
    return params, rank_ofs, data


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


PATHS = {
    "local": lambda p, r, b: dlrm.forward(dlrm.add_remap(p, r), b, CFG),
    "sharded": lambda p, r, b: dlrm.forward(dlrm.add_remap(p, r), b, CFG,
                                            mesh=_one_device_mesh()),
    "sharded_2d": lambda p, r, b: dlrm.forward(
        dlrm.add_remap(p, r), b, CFG, mesh=_one_device_mesh(), hybrid=True,
        table_2d=True),
    "retrieval": lambda p, r, b: dlrm.retrieval_score(
        dlrm.add_remap(p, r),
        {"dense": b["dense"][:1], "indices": b["indices"][:1],
         "candidates": b["indices"][:, 0, 0]}, CFG),
}


def _compiled_text(path: str) -> str:
    params, rank_ofs, batch = _shapes()
    fn = PATHS[path]
    # a new function each time, so no trace is reused across scope settings
    return jax.jit(lambda p, r, b: fn(p, r, b)).lower(
        params, rank_ofs, batch).compile().as_text()


def _strip(text: str) -> str:
    """The program without its metadata: no ``metadata={...}`` and no
    table of source locations before the first computation."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    return "\n".join(re.sub(r",? metadata=\{[^}]*\}", "", line)
                     for line in [lines[0]] + lines[first:])


@pytest.mark.parametrize("path", sorted(PATHS))
def test_each_scope_names_some_op(path):
    op_names = re.findall(r'op_name="([^"]*)"', _compiled_text(path))
    for scope in SCOPES:
        assert any(scope in name.split("/") for name in op_names), scope
    for name in op_names:
        assert sum(scope in name.split("/") for scope in SCOPES) <= 1, name


@pytest.mark.parametrize("path", sorted(PATHS))
def test_scopes_add_no_op(path, monkeypatch):
    with_scopes = _strip(_compiled_text(path))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _compiled_text(path)
    assert not any(f"/{scope}/" in without for scope in SCOPES)
    assert _strip(without) == with_scopes
