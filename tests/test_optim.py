"""Optimizers: convergence on a quadratic, state shapes, partitioned routing."""

import jax
import jax.numpy as jnp
import pytest

from repro import optim


def quad_loss(params):
    return sum((p ** 2).sum() for p in jax.tree.leaves(params))


def run_steps(opt, params, n=30):
    state = opt.init(params)
    losses = [float(quad_loss(params))]
    for _ in range(n):
        grads = jax.grad(quad_loss)(params)
        params, state = opt.update(grads, state, params)
        losses.append(float(quad_loss(params)))
    return params, state, losses


@pytest.fixture
def params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w": jax.random.normal(k1, (8, 4)),
            "tables": [jax.random.normal(k2, (16, 4))]}


class TestOptimizers:
    @pytest.mark.parametrize("make", [
        lambda: optim.sgd(0.1),
        lambda: optim.sgd(0.05, momentum=0.9),
        lambda: optim.adamw(0.05),
        lambda: optim.adagrad(0.5),
        lambda: optim.adagrad(0.5, rowwise=True),
    ])
    def test_decreases_quadratic(self, params, make):
        _, _, losses = run_steps(make(), params)
        assert losses[-1] < 0.2 * losses[0]

    def test_rowwise_adagrad_state_shape(self, params):
        opt = optim.adagrad(0.1, rowwise=True)
        state = opt.init(params)
        assert state["tables"][0].shape == (16,)     # one slot per row
        assert state["w"].shape == (8,)

    def test_adamw_weight_decay(self):
        opt = optim.adamw(0.1, weight_decay=0.1)
        p = {"w": jnp.ones((4,))}
        state = opt.init(p)
        g = {"w": jnp.zeros((4,))}
        new_p, _ = opt.update(g, state, p)
        assert float(new_p["w"][0]) < 1.0            # decay with zero grad

    def test_partitioned_routes_by_label(self, params):
        opt = optim.partitioned(
            lambda ks: "table" if "tables" in ks else "dense",
            {"table": optim.adagrad(0.5, rowwise=True),
             "dense": optim.adamw(0.05)})
        new_params, state, losses = run_steps(opt, params)
        assert losses[-1] < 0.3 * losses[0]
        # rowwise accumulator exists only for the table group
        table_state = state["table"]
        assert any(v.ndim == 1 and v.shape[0] == 16
                   for v in jax.tree.leaves(table_state))

    def test_partitioned_preserves_structure(self, params):
        opt = optim.partitioned(
            lambda ks: "table" if "tables" in ks else "dense",
            {"table": optim.sgd(0.1), "dense": optim.sgd(0.1)})
        state = opt.init(params)
        grads = jax.grad(quad_loss)(params)
        new_params, _ = opt.update(grads, state, params)
        assert jax.tree.structure(new_params) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(new_params),
                        jax.tree.leaves(params), strict=True):
            assert a.shape == b.shape
