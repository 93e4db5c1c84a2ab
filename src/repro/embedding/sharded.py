"""Distributed embedding lookup: row-sharded tables + masked-psum bags.

JAX/XLA lowers a plain ``jnp.take`` on a row-sharded operand to an all-gather
of the *table* when it cannot prove locality — catastrophic for 10^6..10^9-row
tables. The standard TPU recipe (and the shard-level analogue of the paper's
plane-parallel SLS) is explicit:

  * each "model" shard holds ``V / M`` contiguous stored rows;
  * every shard translates the (replicated-over-model) indices to its local
    range, gathers with clamping, masks out-of-range rows to zero;
  * the pooled bag is ``psum`` over the model axis — collective volume is
    ``batch x dim`` (the SLS *output*), never the table.

Combined with ``RemapSpec(plane_distribute=True)`` the hot rows are striped
across shards, so the psum partial work is balanced (PD, Fig. 5c at shard
granularity).

The pooled lookup runs under the named scope ``sls`` and the two-phase
translation under ``translate``, as on the single-device path of
``repro.models.dlrm``, so a profile splits the two.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map


def local_shard_lookup(local_table: jax.Array, indices: jax.Array,
                       shard_id: jax.Array, rows_per_shard: int) -> jax.Array:
    """Gather ``indices`` (stored-rank space) from this shard's rows.

    Returns (..., L, D) with rows owned by other shards zeroed.
    """
    local = indices - shard_id * rows_per_shard
    ok = (local >= 0) & (local < rows_per_shard)
    clamped = jnp.clip(local, 0, rows_per_shard - 1)
    vecs = jnp.take(local_table, clamped, axis=0)
    return jnp.where(ok[..., None], vecs, 0.0)


def sharded_embedding_bag(table: jax.Array, indices: jax.Array,
                          axis_name: str, mode: str = "sum",
                          scatter: bool = False) -> jax.Array:
    """SLS over a row-sharded table, inside ``shard_map``.

    ``table`` is the *local* shard (rows_per_shard, D); ``indices`` is
    (..., L) in stored-rank space, identical on every shard of ``axis_name``.
    Output (..., D) is fully reduced (every shard gets the pooled bags).

    ``scatter=True`` finishes with ``psum_scatter`` over the leading
    (batch) dim instead of ``psum``: each model shard keeps its 1/M slice
    of the batch — half the wire of an all-reduce, and everything dense
    downstream (interaction + MLPs) then runs batch-split across the model
    axis too ("hybrid sharding", §Perf H3).
    """
    with jax.named_scope("sls"):
        rows_per_shard = table.shape[0]
        shard_id = jax.lax.axis_index(axis_name)
        vecs = local_shard_lookup(table, indices, shard_id, rows_per_shard)
        if mode == "sum":
            pooled = vecs.sum(axis=-2)
        elif mode == "mean":
            pooled = vecs.sum(axis=-2) / indices.shape[-1]
        else:
            raise ValueError(f"unsupported distributed mode {mode!r}")
        if scatter:
            return jax.lax.psum_scatter(pooled, axis_name,
                                        scatter_dimension=0, tiled=True)
        return jax.lax.psum(pooled, axis_name)


def make_sharded_bag(mesh, table_spec: P, index_spec: P, out_spec: P,
                     axis_name: str = "model", mode: str = "sum"):
    """Wrap ``sharded_embedding_bag`` in shard_map for the given mesh."""

    def fn(table, indices):
        return sharded_embedding_bag(table, indices, axis_name, mode)

    return shard_map(fn, mesh=mesh,
                         in_specs=(table_spec, index_spec),
                         out_specs=out_spec, check_vma=False)


def sharded_embedding_bag_2d(table: jax.Array, indices: jax.Array,
                             rank_of: jax.Array | None = None,
                             model_axis: str = "model",
                             data_axis: str = "data",
                             mode: str = "sum") -> jax.Array:
    """SLS over a 2D row-sharded table — rows split over (model x data).

    The 1D layout replicates each table over ``data``, so data-parallel
    training must all-reduce *dense table gradients* every step (measured:
    11.3 GB/step/device on dlrm-mlperf — the entire collective bottleneck).
    Sharding rows over both axes gives every row exactly one owner: no
    gradient replication, 256x less table state per device, and the only
    collectives are an index all-gather (MBs) and the bag psum_scatter.

    Inside shard_map: ``table`` (V/(M*D), dim) local rows; ``indices``
    (B/D, L) this data-shard's batch; optional ``rank_of`` (V/(M*D),) local
    slice of the logical->rank hash table (two-phase remapped lookup).
    Returns (B/(D*M), dim): batch scattered over (data, model) — the
    hybrid-sharded layout the dense path consumes.
    """
    rows_per_shard = table.shape[0]
    with jax.named_scope("sls"):
        idx_full = jax.lax.all_gather(indices, data_axis, axis=0, tiled=True)
        sid = (jax.lax.axis_index(model_axis) * jax.lax.axis_size(data_axis)
               + jax.lax.axis_index(data_axis))
    if rank_of is not None:
        # phase 1: logical id -> stored rank through the sharded hash table
        with jax.named_scope("translate"):
            local = idx_full - sid * rows_per_shard
            ok = (local >= 0) & (local < rows_per_shard)
            clamped = jnp.clip(local, 0, rows_per_shard - 1)
            ranks = jnp.where(ok, jnp.take(rank_of, clamped, axis=0), 0)
            idx_full = jax.lax.psum(ranks, (data_axis, model_axis))
    with jax.named_scope("sls"):
        vecs = local_shard_lookup(table, idx_full, sid, rows_per_shard)
        if mode == "sum":
            pooled = vecs.sum(axis=-2)
        elif mode == "mean":
            pooled = vecs.sum(axis=-2) / indices.shape[-1]
        else:
            raise ValueError(f"unsupported distributed mode {mode!r}")
        return jax.lax.psum_scatter(pooled, (data_axis, model_axis),
                                    scatter_dimension=0, tiled=True)


def sharded_remapped_bag(table: jax.Array, rank_of: jax.Array,
                         indices: jax.Array, axis_name: str,
                         mode: str = "sum",
                         scatter: bool = False) -> jax.Array:
    """Frequency-remapped SLS with a *sharded* hash table (two-phase).

    This is the paper's FTL hash-table lookup at shard granularity: the
    logical->rank translation array (``rank_of``, the hash table) is itself
    row-sharded — each shard translates the ids it owns and a small integer
    psum assembles the rank vector — then the rank-space masked-psum SLS
    runs as usual. Total collective volume: (batch x bag) int32 + the
    (batch x dim) output psum. Nothing table-sized ever moves.

    ``table`` (rows/shard, D) is stored rank-ordered; ``rank_of``
    (rows/shard,) holds the ranks of this shard's *logical* id range.
    """
    with jax.named_scope("translate"):          # phase 1
        rows_per_shard = rank_of.shape[0]
        shard_id = jax.lax.axis_index(axis_name)
        local = indices - shard_id * rows_per_shard
        ok = (local >= 0) & (local < rows_per_shard)
        clamped = jnp.clip(local, 0, rows_per_shard - 1)
        ranks = jnp.where(ok, jnp.take(rank_of, clamped, axis=0), 0)
        ranks = jax.lax.psum(ranks, axis_name)
    return sharded_embedding_bag(table, ranks, axis_name, mode,
                                 scatter=scatter)
