"""Frequency-remapped two-tier table layout — the TPU half of RecFlash.

The paper's AF remap co-locates hot rows inside flash pages; on TPU the same
statistics drive a *storage permutation* of each embedding table:

  stored[rank] = logical[perm[rank]]        perm = AccessStats.rank_order()

so the hottest rows occupy a compact prefix. That prefix (the ``hot_size``
first rows) is the page-wise-cache analogue: it is small enough to pin in
VMEM inside the Pallas SLS kernel, while the cold tail stays in HBM. All
lookups translate logical ids through ``rank_of`` (the paper's hash table —
an int32 gather) and read the stored table.

The permutation also fixes shard load balance for the distributed lookup: a
plain frequency sort would pile every hot row onto model-shard 0 (the paper's
"hot items clustered in a few planes", Fig. 5b). ``plane_distribute=True``
applies the paper's PD fix at shard granularity — hot ranks are strided
round-robin across shards so each shard holds an equal slice of hot traffic.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.embedding.bag import LANES


@dataclasses.dataclass
class RemapSpec:
    """Host-side remap plan for one table (built from AccessStats)."""

    perm: np.ndarray        # (V,) rank -> logical row
    rank_of: np.ndarray     # (V,) logical row -> rank (inverse perm)
    hot_size: int           # leading ranks resident in VMEM
    n_shards: int = 1       # model-parallel shards (for PD striping)

    @classmethod
    def from_counts(cls, counts: np.ndarray, hot_frac: float = 0.002,
                    n_shards: int = 1, plane_distribute: bool = True,
                    hot_size: int | None = None) -> "RemapSpec":
        v = counts.shape[0]
        order = np.argsort(-counts, kind="stable")
        if hot_size is None:
            hot_size = max(1, int(round(v * hot_frac)))
        if n_shards > 1 and plane_distribute:
            # PD at shard granularity: stride ranks over shards so that each
            # shard's local prefix holds an equal share of hot rows.
            # rank r lands on shard r % n_shards at local rank r // n_shards;
            # stored layout is shard-major: [shard0 rows..., shard1 rows...].
            r = np.arange(v)
            shard = r % n_shards
            local = r // n_shards
            rows_per_shard = -(-v // n_shards)
            pos = shard * rows_per_shard + local
            new_order = np.empty(v, dtype=np.int64)
            new_order[pos[pos < v]] = order[pos < v]
            # tail positions beyond v (uneven split) folded back
            overflow = pos >= v
            if overflow.any():
                free = np.setdiff1d(np.arange(v), pos[~overflow],
                                    assume_unique=False)
                new_order[free] = order[overflow]
            order = new_order
        rank_of = np.empty(v, dtype=np.int64)
        rank_of[order] = np.arange(v)
        return cls(perm=order.astype(np.int64), rank_of=rank_of,
                   hot_size=int(hot_size), n_shards=n_shards)

    @classmethod
    def identity(cls, v: int, hot_size: int = 1) -> "RemapSpec":
        r = np.arange(v, dtype=np.int64)
        return cls(perm=r, rank_of=r.copy(), hot_size=hot_size)


def remap_table(table: jax.Array, spec: RemapSpec) -> jax.Array:
    """Materialise the stored (rank-ordered) table from the logical one."""
    return jnp.take(table, jnp.asarray(spec.perm), axis=0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedRanks:
    """The ``rank_of`` hash tables of ``tables`` tables stored lane-dense, as
    one array of 128-lane lines: entry ``i`` of table ``t`` is element
    ``t*rows + i`` of ``lines`` flattened, and entries past a shorter
    table's end are zeros. ``translate`` looks up every table in one gather
    of lines; iterating gives each table's logical ``(rows,)`` array.

    A TPU step given one 4-MB ``rank_of`` array per table copies each into
    fast memory every step, eight device ops a table, and given them
    stacked as ``(tables, rows)`` it gathers single int32 entries from
    HBM, twice as slowly."""

    lines: jax.Array
    tables: int = dataclasses.field(metadata={"static": True})
    rows: int = dataclasses.field(metadata={"static": True})

    @classmethod
    def stack(cls, rank_ofs: list[np.ndarray]) -> "PackedRanks":
        """From each table's host ``rank_of`` array, onto the device."""
        rows = max(r.size for r in rank_ofs)
        flat = np.zeros(-(-len(rank_ofs) * rows // LANES) * LANES, np.int32)
        for t, r in enumerate(rank_ofs):
            flat[t * rows:t * rows + r.size] = r
        return cls(jnp.asarray(flat.reshape(-1, LANES)), len(rank_ofs), rows)

    def __iter__(self):
        flat = self.lines.reshape(-1)
        return iter([flat[t * self.rows:(t + 1) * self.rows]
                     for t in range(self.tables)])

    def translate(self, indices: jax.Array) -> jax.Array:
        """Ranks of the logical ids ``indices`` (..., tables, L): line
        ``id // 128`` of each, then its lane."""
        flat = indices + (self.rows * jnp.arange(self.tables,
                                                 dtype=indices.dtype))[:, None]
        got = jnp.take(self.lines, flat >> (LANES.bit_length() - 1), axis=0)
        lane = (flat & (LANES - 1))[..., None]
        return jnp.where(lane == jnp.arange(LANES), got, 0).sum(axis=-1)


def translate(indices: jax.Array, spec: RemapSpec) -> jax.Array:
    """Logical ids -> stored ranks (the paper's hash-table lookup)."""
    return jnp.take(jnp.asarray(spec.rank_of), indices, axis=0)


def lookup_remapped(stored: jax.Array, rank_of: jax.Array,
                    indices: jax.Array) -> jax.Array:
    """Gather logical ``indices`` from a rank-ordered stored table."""
    return jnp.take(stored, jnp.take(rank_of, indices, axis=0), axis=0)
