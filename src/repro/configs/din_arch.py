"""din [arXiv:1706.06978; recsys] — embed 18, seq 100, attn MLP 80-40,
MLP 200-80, target attention. 1M-item table row-sharded over model."""

import functools

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchBundle, StepDef, register, sds
from repro.configs.recsys_common import (RECSYS_SHAPES, build_plan_generic,
                                         recsys_opt_rules, recsys_optimizer)
from repro.models import din

CONFIG = din.DINConfig(n_items=1_000_000)

PARAM_RULES = [("items", P("model", None))]


def make_batch(shape_name):
    def fn(dp):
        shp = RECSYS_SHAPES[shape_name]
        b = shp["batch"]
        batch = {
            "hist": sds((b, CONFIG.seq_len), jnp.int32),
            "hist_mask": sds((b, CONFIG.seq_len), jnp.bool_),
            "profile": sds((b, CONFIG.n_profile), jnp.float32),
        }
        if shape_name == "train_batch":
            batch["target"] = sds((b,), jnp.int32)
            batch["labels"] = sds((b,), jnp.float32)
        elif shape_name == "retrieval_cand":
            batch["candidates"] = sds((shp["n_candidates"],), jnp.int32)
        else:
            batch["target"] = sds((b,), jnp.int32)
        return batch
    return fn


def batch_axes_map(shape_name):
    def fn(batch, axes):
        import jax
        specs = jax.tree.map(
            lambda x: P(axes, *([None] * (len(x.shape) - 1))), batch)
        if shape_name == "retrieval_cand":
            specs = jax.tree.map(lambda s: P(*([None] * len(s))), specs)
            specs["candidates"] = P(axes)
        return specs
    return fn


def _loss(p, batch, mesh, axes):
    return din.loss(p, batch, CONFIG)


def _fwd(p, batch, mesh, axes):
    return din.forward(p, batch, CONFIG)


def _retr(p, batch, mesh, axes):
    return din.retrieval_score(p, batch, CONFIG)


@register("din")
def build():
    bundle = ArchBundle(
        name="din", family="recsys", cfg=CONFIG,
        init=functools.partial(din.init, cfg=CONFIG),
        steps={}, param_rules=PARAM_RULES, optimizer=recsys_optimizer(),
        notes="item table row-sharded; target attention dense")
    bundle.opt_rules = recsys_opt_rules(PARAM_RULES)
    for s in RECSYS_SHAPES:
        kwargs = dict(shape_name=s, make_batch=make_batch(s),
                      batch_axes_map=batch_axes_map(s))
        if s == "train_batch":
            kwargs["loss_fn"] = _loss
        elif s == "retrieval_cand":
            kwargs["fwd_fn"] = _retr
        else:
            kwargs["fwd_fn"] = _fwd
        bundle.steps[s] = StepDef(
            "train" if s == "train_batch" else "serve",
            functools.partial(build_plan_generic, **kwargs), None)
    bundle.model_flops = {
        s: CONFIG.flops_per_sample() * RECSYS_SHAPES[s].get(
            "n_candidates", RECSYS_SHAPES[s]["batch"]) *
        (3.0 if s == "train_batch" else 1.0)
        for s in RECSYS_SHAPES}
    return bundle
