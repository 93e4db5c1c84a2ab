"""dlrm-mlperf [arXiv:1906.00091; recsys] — MLPerf DLRM (Criteo 1TB):
13 dense + 26 sparse fields, embed 128, bot 13-512-256-128,
top 1024-1024-512-256-1, dot interaction, one-hot lookups.

Vocab sizes are the MLPerf Criteo-1TB table sizes, rounded up to multiples
of 512 so each table row-shards evenly over the 16-way model axis. Remap
(the paper's RecFlash hash table) is on: rank_of buffers ride in the batch
(non-trainable) and the two-phase sharded translation feeds the SLS.
"""

import functools

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchBundle, StepDef, register, sds
from repro.configs.recsys_common import (RECSYS_SHAPES, build_plan_generic,
                                         recsys_opt_rules, recsys_optimizer)
from repro.models import dlrm

MLPERF_VOCABS = [39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63,
                 38532951, 2953546, 403346, 10, 2208, 11938, 155, 4, 976,
                 14, 39979771, 25641295, 39664984, 585935, 12972, 108, 36]


def _pad512(v: int) -> int:
    return max(512, (v + 511) // 512 * 512)


def make_config(name="dlrm-mlperf", dim=128, bot=(13, 512, 256, 128),
                top=(1024, 1024, 512, 256, 1), vocabs=None, lookups=1):
    vocabs = vocabs or [_pad512(v) for v in MLPERF_VOCABS]
    return dlrm.DLRMConfig(
        name=name, n_tables=len(vocabs), n_dense=bot[0], embed_dim=dim,
        n_rows=tuple(vocabs), lookups=lookups,
        bot_mlp=tuple(bot[1:]), top_mlp=tuple(top[:-1]))


CONFIG = make_config()

PARAM_RULES = [("tables", P("model", None))]   # MLPs replicated (tiny)
PARAM_RULES_2D = [("tables", P(("model", "data"), None))]


def make_batch(cfg, shape_name, remap=True):
    def fn(dp):
        shp = RECSYS_SHAPES[shape_name]
        b = shp["batch"]
        batch = {
            "dense": sds((b, cfg.n_dense), jnp.float32),
            "indices": sds((b, cfg.n_tables, cfg.lookups), jnp.int32),
        }
        if shape_name == "train_batch":
            batch["labels"] = sds((b,), jnp.float32)
        if shape_name == "retrieval_cand":
            batch["candidates"] = sds((shp["n_candidates"],), jnp.int32)
        if remap:
            batch["rank_of"] = [sds((v,), jnp.int32) for v in cfg.n_rows]
        return batch
    return fn


def batch_axes_map(cfg, shape_name):
    def fn(batch, axes):
        import jax
        specs = jax.tree.map(
            lambda x: P(axes, *([None] * (len(x.shape) - 1))), batch)
        if "rank_of" in batch:
            specs["rank_of"] = [P("model") for _ in batch["rank_of"]]
        if shape_name == "retrieval_cand":
            # the single user row cannot shard over data; candidates do.
            specs["dense"] = P(None, None)
            specs["indices"] = P(None, None, None)
            specs["candidates"] = P(axes)
        return specs
    return fn


def _attach(p, batch):
    return ({**p, "rank_of": batch["rank_of"]}
            if "rank_of" in batch else p)


def loss_fn(cfg, hybrid=False, table_2d=False):
    def fn(p, batch, mesh, axes):
        return dlrm.loss(_attach(p, batch), batch, cfg, mesh, axes,
                         hybrid=hybrid, table_2d=table_2d)
    return fn


def fwd_fn(cfg, retrieval=False, hybrid=False, table_2d=False):
    def fn(p, batch, mesh, axes):
        if retrieval:
            # 1M candidates don't divide (data x model); hybrid stays off
            return dlrm.retrieval_score(_attach(p, batch), batch, cfg,
                                        mesh, axes)
        return dlrm.forward(_attach(p, batch), batch, cfg, mesh, axes,
                            hybrid=hybrid, table_2d=table_2d)
    return fn


def make_dlrm_bundle(name, cfg, remap=True, hybrid=False, table_2d=False):
    """``table_2d`` requires every vocab divisible by 256 (model x data)."""
    # mlperf-size tables (40M rows x 128) train in bf16 with f32 row-wise
    # adagrad accumulators — the industry-standard footprint; fp32 tables
    # alone would be 12 GB/device of params+grads on the 16-way model axis.
    dtype = jnp.bfloat16 if max(cfg.n_rows) > 2_000_000 else jnp.float32
    bundle = ArchBundle(
        name=name, family="recsys", cfg=cfg,
        init=functools.partial(dlrm.init, cfg=cfg, dtype=dtype),
        steps={}, param_rules=PARAM_RULES_2D if table_2d else PARAM_RULES,
        optimizer=recsys_optimizer(),
        notes="row-sharded tables, masked-psum SLS, RecFlash remap "
              + ("on" if remap else "off"))
    rules = PARAM_RULES_2D if table_2d else PARAM_RULES
    if table_2d:
        from jax.sharding import PartitionSpec as _P
        bundle.opt_rules = [("['table'][", _P(("model", "data")))] + rules
    else:
        bundle.opt_rules = recsys_opt_rules(rules)
    for s in RECSYS_SHAPES:
        kwargs = dict(shape_name=s, make_batch=make_batch(cfg, s, remap),
                      batch_axes_map=batch_axes_map(cfg, s))
        if s == "train_batch":
            # training layout: 2D row-sharded tables (no dense table-grad
            # all-reduce — §Perf H3)
            kwargs["loss_fn"] = loss_fn(cfg, hybrid=hybrid,
                                        table_2d=table_2d)
        else:
            # serving layout: 1D (model-only) tables — inference has no
            # gradient to save, and 2D costs an extra index gather +
            # wider reduction (measured: serve_bulk wire 0.21 -> 3.6 GB).
            # Tables are resharded at deployment.
            kwargs["fwd_fn"] = fwd_fn(cfg, retrieval=(s == "retrieval_cand"),
                                      hybrid=hybrid, table_2d=False)
            if table_2d:
                kwargs["param_rules_override"] = PARAM_RULES
        bundle.steps[s] = StepDef(
            "train" if s == "train_batch" else "serve",
            functools.partial(build_plan_generic, **kwargs), None)
    bundle.model_flops = {
        s: cfg.flops_per_sample() * RECSYS_SHAPES[s].get(
            "n_candidates", RECSYS_SHAPES[s]["batch"]) *
        (3.0 if s == "train_batch" else 1.0)
        for s in RECSYS_SHAPES}
    return bundle


@register("dlrm-mlperf")
def build():
    # §Perf H3 layout: hybrid dense sharding + 2D row-sharded tables
    # (vocabs are padded to /512, so they divide the 256-way grid).
    return make_dlrm_bundle("dlrm-mlperf", CONFIG, hybrid=True,
                            table_2d=True)
