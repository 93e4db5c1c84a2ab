"""graphsage-reddit [arXiv:1706.02216; gnn] — 2 layers, d_hidden 128, mean
aggregator, sample sizes 25-10.

Four shapes, three execution regimes:
  full_graph_sm  Cora-scale full batch (2,708 nodes / 10,556 edges / 1,433
                 feats) — graph too small to shard; replicated cell.
  minibatch_lg   Reddit-scale sampled training: each data shard samples its
                 own block (1,024 global seeds / dp), fanout 15-10, padded
                 fixed shapes; the leading dim is the shard axis.
  ogb_products   full-batch large (2,449,029 nodes / 61,859,140 edges,
                 padded to /512 for even edge sharding, d_feat 100).
  molecule       128 graphs x 30 nodes x 64 edges, graph classification,
                 batch-sharded vmapped segment_sum.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import optim
from repro.configs.base import ArchBundle, CellPlan, StepDef, bt_axes, register, sds
from repro.distributed.shardings import make_param_specs
from repro.models import graphsage

# per-shape model configs (d_in/classes follow the dataset of each shape)
CFG_REDDIT = graphsage.SAGEConfig(d_in=602, n_classes=41, fanouts=(15, 10))
CFG_CORA = graphsage.SAGEConfig(d_in=1433, n_classes=7)
CFG_PRODUCTS = graphsage.SAGEConfig(d_in=100, n_classes=47)
CFG_MOLECULE = graphsage.SAGEConfig(d_in=16, n_classes=2)

CONFIG = CFG_REDDIT
PARAM_RULES = []      # 128-wide SAGE weights are tiny -> replicate

SHAPES = {
    "full_graph_sm": dict(n_nodes=2708, n_edges=10556, d_feat=1433),
    "minibatch_lg": dict(n_nodes=232_965, n_edges=114_615_892,
                         batch_nodes=1024, fanouts=(15, 10)),
    "ogb_products": dict(n_nodes=2_449_029, n_edges=61_860_352,  # pad /512
                         d_feat=100),
    "molecule": dict(n_nodes=30, n_edges=64, batch=128),
}


def _plan_full(bundle, mesh, multi_pod, *, cfg, shp, shard_edges):
    axes = bt_axes(multi_pod)
    params = jax.eval_shape(
        functools.partial(graphsage.init, cfg=cfg), jax.random.PRNGKey(0))
    n, e = shp["n_nodes"], shp["n_edges"]
    batch = {"feats": sds((n, cfg.d_in), jnp.float32),
             "edge_src": sds((e,), jnp.int32),
             "edge_dst": sds((e,), jnp.int32),
             "labels": sds((n,), jnp.int32),
             "train_mask": sds((n,), jnp.float32)}
    espec = P(axes) if shard_edges else P()
    b_specs = {"feats": P(), "edge_src": espec, "edge_dst": espec,
               "labels": P(), "train_mask": P()}
    p_specs = make_param_specs(params, bundle.param_rules)
    opt = bundle.optimizer
    opt_state = jax.eval_shape(opt.init, params)
    o_specs = make_param_specs(opt_state, bundle.param_rules)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: graphsage.loss_node(p, batch, cfg, mode="full"))(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return CellPlan(train_step, (params, opt_state, batch),
                    (p_specs, o_specs, b_specs),
                    (p_specs, o_specs, P()), donate=(0, 1))


def _plan_minibatch(bundle, mesh, multi_pod, *, cfg):
    axes = bt_axes(multi_pod)
    dp = 32 if multi_pod else 16
    seeds = SHAPES["minibatch_lg"]["batch_nodes"] // dp   # per shard
    f1, f0 = cfg.fanouts[1], cfg.fanouts[0]               # 10 near seeds, 15
    n1 = seeds * (f1 + 1)
    n0 = n1 * (f0 + 1)
    params = jax.eval_shape(
        functools.partial(graphsage.init, cfg=cfg), jax.random.PRNGKey(0))
    batch = {
        "feats": sds((dp, n0, cfg.d_in), jnp.float32),
        "nbrs": [sds((dp, n1, f0), jnp.int32),
                 sds((dp, seeds, f1), jnp.int32)],
        "self_idx": [sds((dp, n1), jnp.int32),
                     sds((dp, seeds), jnp.int32)],
        "mask": [sds((dp, n1, f0), jnp.bool_),
                 sds((dp, seeds, f1), jnp.bool_)],
        "labels": sds((dp, seeds), jnp.int32),
    }
    b_specs = jax.tree.map(
        lambda x: P(axes, *([None] * (len(x.shape) - 1))), batch)
    p_specs = make_param_specs(params, bundle.param_rules)
    opt = bundle.optimizer
    opt_state = jax.eval_shape(opt.init, params)
    o_specs = make_param_specs(opt_state, bundle.param_rules)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            losses = jax.vmap(
                lambda blk: graphsage.loss_node(p, blk, cfg,
                                                mode="sampled"))(batch)
            return losses.mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return CellPlan(train_step, (params, opt_state, batch),
                    (p_specs, o_specs, b_specs),
                    (p_specs, o_specs, P()), donate=(0, 1))


def _plan_molecule(bundle, mesh, multi_pod, *, cfg):
    axes = bt_axes(multi_pod)
    shp = SHAPES["molecule"]
    b, n, e = shp["batch"], shp["n_nodes"], shp["n_edges"]
    params = jax.eval_shape(
        functools.partial(graphsage.init, cfg=cfg), jax.random.PRNGKey(0))
    batch = {"x": sds((b, n, cfg.d_in), jnp.float32),
             "edges": sds((b, e, 2), jnp.int32),
             "edge_mask": sds((b, e), jnp.bool_),
             "node_mask": sds((b, n), jnp.bool_),
             "labels": sds((b,), jnp.int32)}
    b_specs = jax.tree.map(
        lambda x: P(axes, *([None] * (len(x.shape) - 1))), batch)
    p_specs = make_param_specs(params, bundle.param_rules)
    opt = bundle.optimizer
    opt_state = jax.eval_shape(opt.init, params)
    o_specs = make_param_specs(opt_state, bundle.param_rules)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            logits = graphsage.forward_batched_graphs(
                p, batch["x"], batch["edges"], batch["edge_mask"],
                batch["node_mask"], cfg)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            return -jnp.take_along_axis(
                logp, batch["labels"][:, None], -1).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    return CellPlan(train_step, (params, opt_state, batch),
                    (p_specs, o_specs, b_specs),
                    (p_specs, o_specs, P()), donate=(0, 1))


def _sage_flops(cfg, n_nodes, n_edges) -> float:
    f = 2 * n_edges * cfg.d_in                     # layer-1 aggregate
    f += 2 * n_nodes * cfg.d_in * cfg.d_hidden * 2
    f += 2 * n_edges * cfg.d_hidden                # layer-2 aggregate
    f += 2 * n_nodes * cfg.d_hidden * cfg.d_hidden * 2
    f += 2 * n_nodes * cfg.d_hidden * cfg.n_classes
    return 3.0 * f                                 # fwd+bwd


@register("graphsage-reddit")
def build():
    bundle = ArchBundle(
        name="graphsage-reddit", family="gnn", cfg=CONFIG,
        init=functools.partial(graphsage.init, cfg=CFG_REDDIT),
        steps={}, param_rules=PARAM_RULES,
        optimizer=optim.adamw(1e-3),
        notes="segment_sum message passing; padded-fanout sampled blocks; "
              "per-shape dataset configs (Cora/Reddit/products/molecule)")
    bundle.steps = {
        "full_graph_sm": StepDef("train", functools.partial(
            _plan_full, cfg=CFG_CORA, shp=SHAPES["full_graph_sm"],
            shard_edges=False), None),
        "minibatch_lg": StepDef("train", functools.partial(
            _plan_minibatch, cfg=CFG_REDDIT), None),
        "ogb_products": StepDef("train", functools.partial(
            _plan_full, cfg=CFG_PRODUCTS, shp=SHAPES["ogb_products"],
            shard_edges=True), None),
        "molecule": StepDef("train", functools.partial(
            _plan_molecule, cfg=CFG_MOLECULE), None),
    }
    mb = SHAPES["minibatch_lg"]
    n1 = mb["batch_nodes"] * 11
    n0 = n1 * 16
    bundle.model_flops = {
        "full_graph_sm": _sage_flops(CFG_CORA, 2708, 10556),
        "minibatch_lg": _sage_flops(CFG_REDDIT, n0, n0 * 15),
        "ogb_products": _sage_flops(CFG_PRODUCTS, 2_449_029, 61_860_352),
        "molecule": _sage_flops(CFG_MOLECULE, 128 * 30, 128 * 64),
    }
    return bundle
