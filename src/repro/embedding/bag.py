"""EmbeddingBag in pure JAX (gather + segment-reduce).

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — the bag is built
from ``jnp.take`` + ``jax.ops.segment_sum`` (kernel_taxonomy §RecSys). Two
entry points:

* ``embedding_bag_dense`` — fixed ``(batch, bag)`` index matrices, the DLRM
  multi-hot case; reduction is a plain axis-sum/mean/max (no segment ids
  needed, fastest path on TPU).
* ``embedding_bag_ragged`` — flat indices + offsets (torch EmbeddingBag
  layout), reduced with ``segment_sum`` over bag ids.
* ``embedding_bag_packed`` — the dense bag over a table stored lane-dense
  (``pack_table``): ``p = 128 // D`` rows to each 128-lane line.

A TPU lays a ``(V, D)`` float32 table with ``D < 128`` out column-major, and
a row gather then copies the whole table to row-major with its lanes padded
to 128, at every call. The lane-dense ``(V/p, p·D)`` view holds the same
bytes in the default row-major layout, so its gather reads the table in
place.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

LANES = 128     # the TPU vector register's minor dimension


def embedding_bag_dense(table: jax.Array, indices: jax.Array,
                        mode: str = "sum",
                        weights: jax.Array | None = None) -> jax.Array:
    """Pooled lookup: table (V, D), indices (..., L) -> (..., D)."""
    vecs = jnp.take(table, indices, axis=0)          # (..., L, D)
    if weights is not None:
        vecs = vecs * weights[..., None]
    if mode == "sum":
        return vecs.sum(axis=-2)
    if mode == "mean":
        return vecs.mean(axis=-2)
    if mode == "max":
        return vecs.max(axis=-2)
    raise ValueError(f"unknown mode {mode!r}")


def embedding_bag_ragged(table: jax.Array, indices: jax.Array,
                         segment_ids: jax.Array, num_bags: int,
                         mode: str = "sum",
                         weights: jax.Array | None = None) -> jax.Array:
    """Ragged pooled lookup: flat ``indices`` grouped by ``segment_ids``.

    ``indices``/``segment_ids`` are (N,); output is (num_bags, D).
    """
    vecs = jnp.take(table, indices, axis=0)          # (N, D)
    if weights is not None:
        vecs = vecs * weights[:, None]
    if mode == "sum":
        return jax.ops.segment_sum(vecs, segment_ids, num_segments=num_bags)
    if mode == "mean":
        sums = jax.ops.segment_sum(vecs, segment_ids, num_segments=num_bags)
        cnt = jax.ops.segment_sum(jnp.ones_like(segment_ids, jnp.float32),
                                  segment_ids, num_segments=num_bags)
        return sums / jnp.maximum(cnt, 1.0)[:, None]
    if mode == "max":
        return jax.ops.segment_max(vecs, segment_ids, num_segments=num_bags)
    raise ValueError(f"unknown mode {mode!r}")


def offsets_to_segment_ids(offsets: jax.Array, total: int) -> jax.Array:
    """torch-style bag ``offsets`` (B,) -> per-element segment ids (total,)."""
    return jnp.cumsum(
        jnp.zeros(total, jnp.int32).at[offsets[1:]].add(1)) \
        if offsets.shape[0] > 1 else jnp.zeros(total, jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedTable:
    """A ``(rows, dim)`` table stored as ``lines``, ``(ceil(rows/p), p*dim)``:
    row ``r`` is the ``dim`` lanes from ``(r % p)*dim`` of line ``r // p``,
    and the rows past ``rows`` are zeros. ``dlrm`` gathers from ``lines``;
    code that takes the table for an array (``jnp.take``, a reference
    check) reads the logical ``(rows, dim)`` table (``__jax_array__``)."""

    lines: jax.Array
    rows: int = dataclasses.field(metadata={"static": True})
    dim: int = dataclasses.field(metadata={"static": True})

    def __jax_array__(self) -> jax.Array:
        return self.lines.reshape(-1, self.dim)[:self.rows]


def rows_per_line(dim: int) -> int:
    """How many rows of width ``dim`` one 128-lane line holds: ``128 // dim``
    where ``dim`` divides 128, else 1 (the table is stored as it is)."""
    return LANES // dim if dim < LANES and LANES % dim == 0 else 1


def pack_table(table: jax.Array) -> PackedTable | jax.Array:
    """Store ``table`` (V, D) lane-dense, or as it is where a line holds one
    row. The rows are padded with zeros to a multiple of ``p``."""
    rows, dim = table.shape
    p = rows_per_line(dim)
    if p == 1:
        return table
    padded = jnp.pad(table, ((0, -rows % p), (0, 0)))
    # lines[i, j*dim + d] = padded[i*p + j, d], spelled as a transpose: the
    # TPU compiler turns the plain reshape, fused with a gather that makes
    # the table (``launch/serve.py``), into ~33 MB of code, and this into
    # under 1 MB.
    lines = padded.T.reshape(dim, -1, p).transpose(1, 2, 0)
    return PackedTable(lines.reshape(-1, p * dim), rows, dim)


def embedding_bag_packed(packed: jax.Array, rank: jax.Array,
                         dim: int) -> jax.Array:
    """Sum-pooled lookup of rows ``rank`` (..., L) of a table stored as the
    lines ``packed`` (V/p, p*dim) -> (..., dim): the gather takes line
    ``rank // p`` (a shift: ``p`` is a power of two), and the row's ``dim``
    lanes, at ``(rank % p)*dim``, are rotated to the front of the line.
    The same float32 values are summed in the same order as
    ``embedding_bag_dense`` on the logical table. (Selecting the lanes by
    slicing the line instead makes the TPU compiler transpose every
    gathered block before the sum.)"""
    p = packed.shape[1] // dim
    if p == 1:
        return embedding_bag_dense(packed, rank)
    lines = jnp.take(packed, rank >> (p.bit_length() - 1), axis=0)
    lane = (rank & (p - 1))[..., None]
    vecs = lines
    for j in range(1, p):
        vecs = jnp.where(lane == j, jnp.roll(lines, -j * dim, axis=-1), vecs)
    return vecs.sum(axis=-2)[..., :dim]
