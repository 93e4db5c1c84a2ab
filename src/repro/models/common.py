"""Shared pure-JAX building blocks (no flax — params are nested dicts)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def normal_init(key, shape, stddev, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * stddev


def dense_init(key, d_in, d_out, dtype=jnp.float32, bias=False):
    """He/LeCun-style fan-in init for a linear layer."""
    w = normal_init(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params, x):
    y = x @ params["w"]
    if "b" in params:
        y = y + params["b"]
    return y


def mlp_init(key, sizes, dtype=jnp.float32, bias=True):
    keys = jax.random.split(key, len(sizes) - 1)
    return [dense_init(k, a, b, dtype, bias)
            for k, a, b in zip(keys, sizes[:-1], sizes[1:], strict=True)]


def mlp(params, x, act=jax.nn.relu, final_act=None):
    for i, layer in enumerate(params):
        x = dense(layer, x)
        if i < len(params) - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


def layer_norm(x, gamma, beta, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def ln_init(d, dtype=jnp.float32):
    return {"gamma": jnp.ones((d,), dtype), "beta": jnp.zeros((d,), dtype)}
