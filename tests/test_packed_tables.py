"""Lane-dense table storage: the packed gather and the served step on it.

``pack_table`` stores a ``(V, D)`` table as ``(ceil(V/p), p*D)`` lines with
``p = 128 // D``; ``embedding_bag_packed`` must return exactly what
``embedding_bag_dense`` returns on the logical table. ``PackedRanks`` stores
the rank_of hash tables as one array of 128-lane lines; its ``translate``
must return exactly what a take from each table's array returns. The
served step on what ``place_tables`` stored must score as ``dlrm.forward``
does on the logical tables, whatever number of its rows are live (differ
from row 0) and so whichever row bucket it runs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.dlrm as dlrm
from repro.embedding.bag import (PackedTable, embedding_bag_dense,
                                 embedding_bag_packed, pack_table,
                                 rows_per_line)
from repro.embedding.layout import PackedRanks, RemapSpec, remap_table
from repro.launch import serve


@pytest.mark.parametrize("dim,p", [(16, 8), (32, 4), (64, 2), (96, 1),
                                   (128, 1)])
def test_packed_bag_equals_dense(dim, p):
    """V = 8p + 3 rows, so the last line is padded (except where p = 1); the
    ranks include 0, V - 1 and every row of the last line."""
    rows = 8 * p + 3
    table = jax.random.normal(jax.random.PRNGKey(dim), (rows, dim))
    stored = pack_table(table)
    assert rows_per_line(dim) == p
    if p == 1:
        assert stored is table
        lines = table
    else:
        assert isinstance(stored, PackedTable)
        lines = stored.lines
        assert lines.shape == (-(-rows // p), p * dim)
        assert not np.asarray(lines[-1, (rows % p) * dim:]).any()
        np.testing.assert_array_equal(jnp.asarray(stored), table)
    last_line = range(rows - rows % p, rows)
    rng = np.random.default_rng(dim)
    rank = np.concatenate([[0, rows - 1], list(last_line),
                           rng.integers(0, rows, 5 * 12 - 2 - len(last_line))])
    rank = jnp.asarray(rank.reshape(5, 12), jnp.int32)
    got = jax.jit(embedding_bag_packed, static_argnums=2)(lines, rank, dim)
    want = jax.jit(embedding_bag_dense)(table, rank)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows", [(101, 64, 37), (128, 128), (1000,) * 3])
def test_packed_ranks_translate_as_each_table(rows):
    """Ids 0 and V - 1 of every table and random ones; tables of unequal
    rows, lines that hold the end of one table and the start of the next."""
    rng = np.random.default_rng(len(rows))
    rank_ofs = [rng.permutation(n).astype(np.int32) for n in rows]
    ranks = PackedRanks.stack(rank_ofs)
    assert ranks.lines.shape == (-(-len(rows) * max(rows) // 128), 128)
    for got, want in zip(ranks, rank_ofs, strict=True):
        np.testing.assert_array_equal(np.asarray(got)[:want.size], want)
    idx = np.stack([np.concatenate([[0, n - 1], rng.integers(0, n, 10)])
                    for n in rows], axis=0)[None].repeat(3, axis=0)
    got = jax.jit(PackedRanks.translate)(ranks, jnp.asarray(idx, jnp.int32))
    want = np.stack([r[idx[:, t]] for t, r in enumerate(rank_ofs)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), want)


def _arch(dim: int) -> dlrm.DLRMConfig:
    return dlrm.DLRMConfig(name=f"packed{dim}", n_tables=3, n_dense=5,
                           embed_dim=dim, n_rows=(101, 64, 37), lookups=6,
                           bot_mlp=(16, dim), top_mlp=(16,))


@pytest.mark.parametrize("dim", [16, 32, 64, 96, 128])
def test_placed_step_equals_forward(dim):
    """``place_tables`` then ``serve_step`` against ``dlrm.forward`` on the
    logical tables: remapped with ``rank_of``, and as initialised with
    logical ids."""
    cfg = _arch(dim)
    rng = np.random.default_rng(dim)
    specs = [RemapSpec.from_counts(rng.integers(0, 40, n)) for n in cfg.n_rows]
    params, rank_ofs = serve.place_tables(cfg, specs, seed=7)
    packed = rows_per_line(dim) > 1
    assert all(isinstance(t, PackedTable) == packed for t in params["tables"])
    batch = {"dense": jnp.asarray(rng.normal(size=(9, cfg.n_dense)),
                                  jnp.float32),
             "indices": jnp.asarray(np.stack(
                 [rng.integers(0, n, (9, cfg.lookups)) for n in cfg.n_rows],
                 axis=1), jnp.int32)}
    got = np.asarray(serve.serve_step(params, rank_ofs, batch, cfg=cfg))

    logical = dlrm.init(jax.random.PRNGKey(7), cfg)
    remapped = {**logical, "tables": [remap_table(t, s) for t, s in
                                      zip(logical["tables"], specs,
                                          strict=True)]}
    with_rank_of = dlrm.forward(dlrm.add_remap(remapped, rank_ofs), batch,
                                cfg)
    plain = dlrm.forward(logical, batch, cfg)
    np.testing.assert_allclose(got, np.asarray(with_rank_of), atol=1e-6)
    np.testing.assert_allclose(got, np.asarray(plain), atol=1e-6)


@pytest.mark.parametrize("rows,buckets", [(64, (8, 16, 32, 64)), (9, (8, 9)),
                                          (8, (8,)), (5, (5,)),
                                          (65, (8, 16, 32, 64, 65))])
def test_row_buckets_double_from_a_sublane_tile(rows, buckets):
    assert serve.row_buckets(rows) == buckets


@functools.cache
def _placed_64():
    """``_arch(64)`` placed for the served step, and the same model for
    ``dlrm.forward``: remapped tables and their ``rank_of``."""
    cfg = _arch(64)
    rng = np.random.default_rng(64)
    specs = [RemapSpec.from_counts(rng.integers(0, 40, n)) for n in cfg.n_rows]
    params, rank_ofs = serve.place_tables(cfg, specs, seed=7)
    logical = dlrm.init(jax.random.PRNGKey(7), cfg)
    remapped = {**logical, "tables": [remap_table(t, s) for t, s in
                                      zip(logical["tables"], specs,
                                          strict=True)]}
    return cfg, params, rank_ofs, dlrm.add_remap(remapped, rank_ofs)


@pytest.mark.parametrize("live,case", [
    *[(n, "random") for n in (1, 7, 8, 9, 16, 17, 33, 63, 64)],
    (17, "a middle row equals row 0"),
    (64, "only the last row differs"),
    (10, "only -0.0 where row 0 has 0.0")])
def test_step_on_the_live_rows_equals_forward(live, case):
    """Batches of 64 rows padded by ``serve._pad`` from ``live`` rows: the
    step counts ``live`` rows, so it runs the bucket that holds them, and
    scores every row as ``dlrm.forward`` does on all 64."""
    cfg, params, rank_ofs, reference = _placed_64()
    rng = np.random.default_rng(live)
    dense = rng.normal(size=(live, cfg.n_dense)).astype(np.float32)
    idx = np.stack([rng.integers(0, n, (live, cfg.lookups))
                    for n in cfg.n_rows], axis=1)
    if case == "a middle row equals row 0":
        dense[live // 2], idx[live // 2] = dense[0], idx[0]
    elif case == "only the last row differs":
        dense[1:-1], idx[1:-1] = dense[0], idx[0]
    elif case == "only -0.0 where row 0 has 0.0":
        dense[0, 0] = 0.0
        dense[1:], idx[1:] = dense[0], idx[0]
        dense[-1, 0] = -0.0
    batch = {"dense": jnp.asarray(serve._pad(dense, 64)),
             "indices": jnp.asarray(serve._pad(idx, 64), jnp.int32)}
    assert int(jax.jit(serve._live_rows)(batch)) == live
    got = np.asarray(serve.serve_step(params, rank_ofs, batch, cfg=cfg))
    want = jax.jit(dlrm.forward, static_argnames="cfg")(reference, batch,
                                                        cfg=cfg)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)
