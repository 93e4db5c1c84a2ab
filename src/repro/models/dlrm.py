"""DLRM (Naumov et al., arXiv:1906.00091) — the paper's benchmark model.

dense features -> bottom MLP -> d-dim vector; each sparse field -> SLS
(embedding-bag sum) -> d-dim vector; pairwise-dot feature interaction over
the (n_tables + 1) vectors; concat [bottom_out, interactions] -> top MLP ->
CTR logit. Covers RMC1/RMC2/RMC3 (Table II), dlrm-mlperf and dlrm-rm2.

The embedding path is RecFlash's target: tables can be stored
frequency-remapped (``remap=True`` routes indices through the RemapSpec
translation — the paper's hash table) and, distributed, row-sharded with the
masked-psum SLS of ``repro.embedding.sharded``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.compat import shard_map
from repro.embedding.bag import (PackedTable, embedding_bag_dense,
                                embedding_bag_packed)
from repro.models.common import mlp, mlp_init


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str
    n_tables: int
    n_dense: int
    embed_dim: int
    n_rows: tuple           # per-table vocab sizes (len == n_tables)
    lookups: int            # multi-hot width per table
    bot_mlp: tuple          # hidden sizes; input = n_dense, output = embed_dim
    top_mlp: tuple          # hidden sizes; output = 1
    interaction: str = "dot"

    @property
    def n_vectors(self) -> int:
        return self.n_tables + 1

    @property
    def top_in(self) -> int:
        if self.interaction == "dot":
            n = self.n_vectors
            return self.embed_dim + n * (n - 1) // 2
        return self.n_vectors * self.embed_dim    # concat interaction

    def flops_per_sample(self) -> int:
        """MODEL_FLOPS estimate (fwd): 2*MACs of MLPs + interaction + SLS."""
        f = 0
        sizes = (self.n_dense,) + tuple(self.bot_mlp) + (self.embed_dim,)
        f += sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:], strict=True))
        tsizes = (self.top_in,) + tuple(self.top_mlp) + (1,)
        f += sum(2 * a * b for a, b in zip(tsizes[:-1], tsizes[1:], strict=True))
        f += 2 * self.n_vectors * self.n_vectors * self.embed_dim  # pairwise dot
        f += 2 * self.n_tables * self.lookups * self.embed_dim     # SLS adds
        return f


def make_rmc(name: str, n_tables: int, dim: int, lookups: int,
             bot: tuple, top: tuple, n_rows: int = 1_000_000,
             n_dense: int | None = None) -> DLRMConfig:
    """Table-II helper: sizes listed as `in-h1-..` for bottom, `h..-1` top."""
    return DLRMConfig(name=name, n_tables=n_tables,
                      n_dense=n_dense if n_dense is not None else bot[0],
                      embed_dim=dim, n_rows=(n_rows,) * n_tables,
                      lookups=lookups, bot_mlp=tuple(bot[1:-1]) + (bot[-1],),
                      top_mlp=tuple(top[:-1]))


# Table II (paper) — bottom lists include input dim, tops end with 1.
RMC1 = make_rmc("rmc1", 8, 32, 80, (128, 64, 32), (256, 64, 1))
RMC2 = make_rmc("rmc2", 32, 64, 120, (256, 128, 64), (128, 64, 1))
RMC3 = make_rmc("rmc3", 10, 32, 20, (2560, 1024, 256, 32), (512, 256, 1))


def init(key, cfg: DLRMConfig, dtype=jnp.float32):
    keys = jax.random.split(key, cfg.n_tables + 2)
    tables = []
    for t in range(cfg.n_tables):
        scale = 1.0 / jnp.sqrt(jnp.float32(cfg.n_rows[t]))
        tables.append(jax.random.uniform(
            keys[t], (cfg.n_rows[t], cfg.embed_dim), dtype, -scale, scale))
    bot_sizes = (cfg.n_dense,) + tuple(cfg.bot_mlp)
    if bot_sizes[-1] != cfg.embed_dim:
        bot_sizes = bot_sizes + (cfg.embed_dim,)
    top_sizes = (cfg.top_in,) + tuple(cfg.top_mlp) + (1,)
    return {
        "tables": tables,
        "bot": mlp_init(keys[-2], bot_sizes, dtype),
        "top": mlp_init(keys[-1], top_sizes, dtype),
    }


def interact(bottom_out: jax.Array, bags: jax.Array,
             interaction: str) -> jax.Array:
    """bottom_out (B,D), bags (B,T,D) -> top-MLP input."""
    z = jnp.concatenate([bottom_out[:, None, :], bags], axis=1)  # (B,T+1,D)
    if interaction == "dot":
        dots = jnp.einsum("bid,bjd->bij", z, z)
        n = z.shape[1]
        iu, ju = jnp.triu_indices(n, k=1)
        flat = dots[:, iu, ju]                                    # (B, nC2)
        return jnp.concatenate([bottom_out, flat], axis=1)
    return z.reshape(z.shape[0], -1)


def _bag(params, indices, t: int, mesh, axes, hybrid: bool = False,
         table_2d: bool = False):
    """One table's SLS: local on CPU/smoke; sharded masked-psum under a mesh.

    With remap enabled (``rank_of`` present) the logical->rank hash-table
    translation happens first — sharded, via the two-phase lookup.
    ``hybrid=True`` finishes with psum_scatter: bags come back with the
    batch split over (axes x model). ``table_2d=True`` additionally shards
    table rows over (model x data) — no table replication across data, so
    no dense table-grad all-reduce. The translation runs under the named
    scope ``translate`` and the pooled lookup under ``sls`` (on the sharded
    paths, inside ``repro.embedding.sharded``). A table stored lane-dense
    (``PackedTable``, the served tables) is read by the packed gather.
    """
    table = params["tables"][t]
    if mesh is None:
        idx = indices
        if "rank_of" in params:
            with jax.named_scope("translate"):
                idx = jnp.take(params["rank_of"][t], idx, axis=0)
        with jax.named_scope("sls"):
            if isinstance(table, PackedTable):
                return embedding_bag_packed(table.lines, idx, table.dim)
            return embedding_bag_dense(table, idx)
    from jax.sharding import PartitionSpec as P
    from repro.embedding.sharded import (sharded_embedding_bag,
                                         sharded_embedding_bag_2d,
                                         sharded_remapped_bag)
    # axes=None -> replicated indices (e.g. the batch-1 user side of
    # retrieval scoring, which cannot shard over the data axis).
    ispec = P(axes, None) if axes is not None else P(None, None)
    ospec = P(tuple(axes) + ("model",), None) if hybrid else ispec
    if table_2d and axes is not None:
        tspec = P(("model", "data"), None)
        ro = params.get("rank_of")
        fn = shard_map(
            lambda tb, ix, *r: sharded_embedding_bag_2d(
                tb, ix, r[0] if r else None),
            mesh=mesh,
            in_specs=(tspec, ispec) + ((P(("model", "data")),) if ro
                                       else ()),
            out_specs=P(tuple(axes) + ("model",), None), check_vma=False)
        args = (table, indices) + ((ro[t],) if ro else ())
        return fn(*args)
    if "rank_of" in params:
        fn = shard_map(
            lambda tb, ro, ix: sharded_remapped_bag(tb, ro, ix, "model",
                                                    scatter=hybrid),
            mesh=mesh, in_specs=(P("model", None), P("model"), ispec),
            out_specs=ospec, check_vma=False)
        return fn(table, params["rank_of"][t], indices)
    fn = shard_map(
        lambda tb, ix: sharded_embedding_bag(tb, ix, "model",
                                             scatter=hybrid),
        mesh=mesh, in_specs=(P("model", None), ispec),
        out_specs=ospec, check_vma=False)
    return fn(table, indices)


def _constrain_hybrid(x, mesh, axes):
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(tuple(axes) + ("model",), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def forward(params, batch, cfg: DLRMConfig, mesh=None, axes=("data",),
            hybrid: bool = False, table_2d: bool = False):
    """batch: dense (B,n_dense) f32, indices (B,n_tables,lookups) i32.

    ``hybrid`` splits the batch across (axes x model) for the dense path
    (bottom/top MLP + interaction): the bag psum becomes a psum_scatter
    (half the wire) and the dense compute uses all chips instead of
    running model-ways replicated.

    Each layer runs under one named scope: ``translate`` and ``sls`` (see
    ``_bag``), ``interact``, and ``mlp`` for both MLPs. A scope only names
    the ops in the compiled program's metadata; it adds no op.
    """
    hybrid = hybrid and mesh is not None and axes is not None
    with jax.named_scope("mlp"):
        dense_in = batch["dense"]
        if hybrid:
            dense_in = _constrain_hybrid(dense_in, mesh, axes)
        x = mlp(params["bot"], dense_in)
    bags = [_bag(params, batch["indices"][:, t, :], t, mesh, axes, hybrid,
                 table_2d=hybrid and table_2d)
            for t in range(cfg.n_tables)]
    with jax.named_scope("interact"):
        bags = jnp.stack(bags, axis=1)
        feat = interact(x, bags, cfg.interaction)
    with jax.named_scope("mlp"):
        return mlp(params["top"], feat)[:, 0]      # logits (B,)


def loss(params, batch, cfg: DLRMConfig, mesh=None, axes=("data",),
         hybrid: bool = False, table_2d: bool = False):
    logits = forward(params, batch, cfg, mesh, axes, hybrid, table_2d)
    y = batch["labels"]
    if hybrid and mesh is not None and axes is not None:
        y = _constrain_hybrid(y, mesh, axes)
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def add_remap(params, rank_ofs):
    """Attach per-table logical->rank hash tables (RecFlash layout)."""
    return {**params, "rank_of": list(rank_ofs)}


def retrieval_score(params, batch, cfg: DLRMConfig, mesh=None,
                    axes=("data",)):
    """Score 1 user against N candidates (retrieval_cand shape).

    The user's dense path + all-but-last sparse fields are computed once;
    the last sparse field is swept over ``candidates`` (N,) ids — a batched
    interaction + top-MLP over N rows, no loop.
    """
    with jax.named_scope("mlp"):
        x = mlp(params["bot"], batch["dense"])                  # (1, D)
    fixed = [_bag(params, batch["indices"][:, t, :], t, mesh, None)
             for t in range(cfg.n_tables - 1)]                  # batch 1
    cand = _bag(params, batch["candidates"][:, None],
                cfg.n_tables - 1, mesh, axes)                   # (N, D)
    n = cand.shape[0]
    with jax.named_scope("interact"):
        bags = jnp.concatenate(
            [jnp.broadcast_to(jnp.stack(fixed, 1), (n, cfg.n_tables - 1,
                                                    cfg.embed_dim)),
             cand[:, None, :]], axis=1)
        xb = jnp.broadcast_to(x, (n, cfg.embed_dim))
        feat = interact(xb, bags, cfg.interaction)
    with jax.named_scope("mlp"):
        return mlp(params["top"], feat)[:, 0]                   # (N,)
