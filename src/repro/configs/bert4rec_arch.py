"""bert4rec [arXiv:1904.06690; recsys] — embed 64, 2 blocks, 2 heads,
seq 200, bidirectional self-attention, cloze training (20 masked positions
per sample). Encoder-only: serve cells run full-sequence scoring (its real
serving mode); there is no autoregressive decode (DESIGN.md §4)."""

import functools

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchBundle, StepDef, register, sds
from repro.configs.recsys_common import (RECSYS_SHAPES, build_plan_generic,
                                         recsys_opt_rules, recsys_optimizer)
from repro.models import bert4rec

N_MASK = 20

CONFIG = bert4rec.Bert4RecConfig(n_items=26_752)   # ML-20m, padded /16

PARAM_RULES = [("items", P("model", None))]


def make_batch(shape_name):
    def fn(dp):
        shp = RECSYS_SHAPES[shape_name]
        b = shp["batch"]
        t = CONFIG.seq_len
        batch = {
            "items": sds((b, t), jnp.int32),
            "pad_mask": sds((b, t), jnp.bool_),
        }
        if shape_name == "train_batch":
            batch.update({
                "mask_pos": sds((b, N_MASK), jnp.int32),
                "targets": sds((b, N_MASK), jnp.int32),
                "target_mask": sds((b, N_MASK), jnp.bool_),
            })
        if shape_name == "retrieval_cand":
            batch["candidates"] = sds((shp["n_candidates"],), jnp.int32)
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
    return bert4rec.loss(p, batch, CONFIG)


def _score(p, batch, mesh, axes):
    # serving: next-item logits of the last position, (B, n_items)
    return bert4rec.score(p, batch, CONFIG)


def _retr(p, batch, mesh, axes):
    return bert4rec.retrieval_score(p, batch, CONFIG)


@register("bert4rec")
def build():
    bundle = ArchBundle(
        name="bert4rec", family="recsys", cfg=CONFIG,
        init=functools.partial(bert4rec.init, cfg=CONFIG),
        steps={}, param_rules=PARAM_RULES, optimizer=recsys_optimizer(),
        notes="encoder-only; serve = full-sequence scoring; "
              "item table row-sharded over model")
    bundle.opt_rules = recsys_opt_rules(PARAM_RULES)
    for s in RECSYS_SHAPES:
        kwargs = dict(shape_name=s, make_batch=make_batch(s),
                      batch_axes_map=batch_axes_map(s))
        if s == "train_batch":
            kwargs["loss_fn"] = _loss
            # 16 grad-accumulation chunks: a fused 65k step's (B, 20, 26752)
            # f32 cloze logits alone are ~9 GB/device otherwise.
            kwargs["microbatch"] = 16
        elif s == "retrieval_cand":
            kwargs["fwd_fn"] = _retr
        else:
            kwargs["fwd_fn"] = _score
        bundle.steps[s] = StepDef(
            "train" if s == "train_batch" else "serve",
            functools.partial(build_plan_generic, **kwargs), None)
    bundle.model_flops = {
        s: CONFIG.flops_per_sample() * RECSYS_SHAPES[s].get(
            "n_candidates", RECSYS_SHAPES[s]["batch"]) *
        (3.0 if s == "train_batch" else 1.0)
        for s in RECSYS_SHAPES}
    return bundle
