"""Optimizers (pure JAX, optax-like API but self-contained).

``sgd``, ``adamw`` and ``adagrad`` (with a **row-wise** mode for embedding
tables — one accumulator per row, the industry-standard memory saving for
10^6..10^9-row tables), composed per parameter group by ``partitioned``.

API: ``opt.init(params) -> state``; ``opt.update(grads, state, params) ->
(new_params, new_state)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple]


def sgd(lr: float, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree.map(jnp.zeros_like, params)

    def update(grads, state, params):
        if momentum == 0.0:
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, ()
        vel = jax.tree.map(lambda v, g: momentum * v + g, state, grads)
        new = jax.tree.map(lambda p, v: p - lr * v, params, vel)
        return new, vel

    return Optimizer(init, update)


def adamw(lr: float, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        z = jax.tree.map(jnp.zeros_like, params)
        return {"m": z, "v": jax.tree.map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params):
        t = state["t"] + 1
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                         state["v"], grads)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def step(p, m_, v_):
            upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            return p - lr * (upd + weight_decay * p)

        return (jax.tree.map(step, params, m, v),
                {"m": m, "v": v, "t": t})

    return Optimizer(init, update)


def adagrad(lr: float, eps: float = 1e-10,
            rowwise: bool = False) -> Optimizer:
    """DLRM-style adagrad. ``rowwise`` keeps one accumulator per table row
    (mean over the embedding dim), cutting optimizer memory D-fold."""

    def init(params):
        if rowwise:
            return jax.tree.map(
                lambda p: jnp.zeros(p.shape[:1] if p.ndim == 2 else p.shape,
                                    jnp.float32), params)
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    def update(grads, state, params):
        def upd(p, g, a):
            if rowwise and p.ndim == 2:
                a_new = a + (g.astype(jnp.float32) ** 2).mean(-1)
                scale = jax.lax.rsqrt(a_new + eps)[:, None]
            else:
                a_new = a + g.astype(jnp.float32) ** 2
                scale = jax.lax.rsqrt(a_new + eps)
            return p - lr * g * scale.astype(p.dtype), a_new

        out = jax.tree.map(upd, params, grads, state)
        new_params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_state = jax.tree.map(lambda o: o[1], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
        return new_params, new_state

    return Optimizer(init, update)


def partitioned(label_fn: Callable[[str], str],
                opts: dict[str, Optimizer]) -> Optimizer:
    """Route each param to an optimizer by path label (e.g. embedding tables
    -> row-wise adagrad, dense weights -> adamw).

    ``label_fn`` receives ``jax.tree_util.keystr`` of the leaf path and must
    return a key of ``opts``. Each group is handled as a flat
    {path: leaf} dict (a valid pytree), so any Optimizer composes.
    """
    from jax.tree_util import keystr, tree_flatten_with_path

    def _split(tree):
        leaves, treedef = tree_flatten_with_path(tree)
        groups: dict[str, dict[str, Any]] = {k: {} for k in opts}
        for path, leaf in leaves:
            groups[label_fn(keystr(path))][keystr(path)] = leaf
        return groups, treedef

    def init(params):
        groups, _ = _split(params)
        return {k: opts[k].init(groups[k]) for k in opts}

    def update(grads, state, params):
        pg, treedef = _split(params)
        gg, _ = _split(grads)
        merged: dict[str, Any] = {}
        new_state = {}
        for k, opt in opts.items():
            upd, st = opt.update(gg[k], state[k], pg[k])
            new_state[k] = st
            merged.update(upd)
        leaves, _ = tree_flatten_with_path(params)
        new_leaves = [merged[keystr(path)] for path, _ in leaves]
        return treedef.unflatten(new_leaves), new_state

    return Optimizer(init, update)
