"""Shared plumbing for the four recsys architectures.

Cells per arch: train_batch (65,536-sample train_step with partitioned
optimizer — row-wise adagrad on tables, AdamW on MLPs), serve_p99 (512),
serve_bulk (262,144), retrieval_cand (1 user x 1M candidates).

Embedding tables are row-sharded over ``model`` with the masked-psum SLS
(never gathered); with ``remap=True`` (DLRM archs — the paper's system) the
logical->rank hash table is itself sharded and consulted via the two-phase
translation lookup. Non-trainable buffers (rank_of) ride outside the
differentiated params.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.configs.base import CellPlan, bt_axes
from repro.distributed.shardings import make_param_specs

RECSYS_SHAPES = {
    "train_batch": dict(batch=65_536),
    "serve_p99": dict(batch=512),
    "serve_bulk": dict(batch=262_144),
    "retrieval_cand": dict(batch=1, n_candidates=1_000_000),
}


def recsys_optimizer():
    return optim.partitioned(
        lambda ks: "table" if ("tables" in ks or "items" in ks) else "dense",
        {"table": optim.adagrad(0.01, rowwise=True),
         "dense": optim.adamw(1e-3)})


def recsys_opt_rules(param_rules):
    # row-wise adagrad accumulators are (V,) per table -> shard over model.
    return [("['table'][", P("model"))] + param_rules


def build_plan_generic(bundle, mesh, multi_pod, *, shape_name,
                       make_batch, loss_fn=None, fwd_fn=None,
                       batch_axes_map=None, microbatch: int | None = None,
                       param_rules_override=None):
    """Generic recsys/gnn cell builder.

    ``make_batch(shp, dp)`` returns the batch SDS dict; ``loss_fn(params,
    batch, mesh, axes)`` for train cells, ``fwd_fn`` for serve cells.
    ``batch_axes_map`` optionally overrides per-leaf batch sharding.
    ``microbatch=n`` splits the train batch into ``n`` gradient-accumulation
    chunks (scan + checkpoint): every leaf becomes (n, B/n, ...) with the
    batch sharding on the second dim — the standard fix when a fused
    65k-sample step's activations (e.g. bert4rec's (B, M, vocab) cloze
    logits) blow past HBM.
    """
    axes = bt_axes(multi_pod)
    dp = 32 if multi_pod else 16
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    batch = make_batch(dp)
    p_specs = make_param_specs(params,
                               param_rules_override or bundle.param_rules)
    if batch_axes_map is None:
        b_specs = jax.tree.map(
            lambda x: P(axes, *([None] * (len(x.shape) - 1))), batch)
    else:
        b_specs = batch_axes_map(batch, axes)

    if loss_fn is not None:
        chunk_keys = ()
        if microbatch:
            # chunk only true per-sample leaves (leading dim == global
            # batch); side buffers like dlrm's rank_of hash tables stay
            # whole and are closed over by the accumulation scan.
            bsz = RECSYS_SHAPES[shape_name]["batch"]
            chunk_keys = tuple(
                k for k, v in batch.items()
                if all(leaf.shape[:1] == (bsz,)
                       for leaf in jax.tree.leaves(v)))
            for k in chunk_keys:
                batch[k] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        (microbatch, bsz // microbatch) + x.shape[1:],
                        x.dtype), batch[k])
                b_specs[k] = jax.tree.map(
                    lambda x: P(None, axes, *([None] * (len(x.shape) - 2))),
                    batch[k])
        opt = bundle.optimizer
        opt_state = jax.eval_shape(opt.init, params)
        o_specs = make_param_specs(opt_state, bundle.rules_for_opt())

        def full_loss(p, batch):
            if not microbatch:
                return loss_fn(p, batch, mesh, axes)
            moving = {k: batch[k] for k in chunk_keys}
            static = {k: v for k, v in batch.items() if k not in chunk_keys}

            def body(acc, mb):
                return acc + loss_fn(p, {**static, **mb}, mesh, axes), None

            acc, _ = jax.lax.scan(
                jax.checkpoint(body), jnp.zeros((), jnp.float32), moving)
            return acc / microbatch

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: full_loss(p, batch))(params)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        return CellPlan(fn=train_step, args=(params, opt_state, batch),
                        in_specs=(p_specs, o_specs, b_specs),
                        out_specs=(p_specs, o_specs, P()), donate=(0, 1))

    def serve_step(params, batch):
        return fwd_fn(params, batch, mesh, axes)

    out_spec = P(axes)
    return CellPlan(fn=serve_step, args=(params, batch),
                    in_specs=(p_specs, b_specs), out_specs=out_spec)
