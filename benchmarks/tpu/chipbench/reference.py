"""Plain DLRM reference for the chip benchmark, and its lower-precision control.

The reference imports nothing of the program. It makes the model's logical
weights from the weight seed with the same ``jax.random`` draws the program's
initialisation makes (threefry keys split ``n_tables + 2`` ways; table ``t``
uniform in +-1/sqrt(rows); MLP weights normal / sqrt(fan_in), zero biases),
and scores requests by their logical row ids on the un-remapped tables:

    bags[t]  = sum over lookups of table_t[row]           (SLS)
    x        = bottom MLP(dense), ReLU between layers
    z        = [x, bags[0], ..., bags[T-1]]
    features = [x, z_i . z_j for i < j]                    (dot interaction)
    logit    = top MLP(features)

It runs table by table and in blocks of rows, after the program's state has
been freed, so one table and one block are on the device at a time.

``precision="highest"`` is float32 matmuls (six bf16 passes on a TPU, exact
float32 products on the CPU). ``precision="high3"`` is the control: the same
computation with every matmul in the three-pass bfloat16 scheme that a TPU
runs for ``high`` precision, written out so that it reads the same on the
CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high3")
# what ``logit_gap`` reads for logits that are not finite (or not there):
# above any limit, and still a number in the result's JSON
NOT_A_NUMBER = float(np.finfo(np.float64).max)
HIGHEST = jax.lax.Precision.HIGHEST


def _split3(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def einsum(spec: str, a: jax.Array, b: jax.Array, precision: str
           ) -> jax.Array:
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    a_hi, a_lo = _split3(a)
    b_hi, b_lo = _split3(b)
    e = functools.partial(jnp.einsum, spec, precision=HIGHEST)
    return e(a_hi, b_hi) + (e(a_hi, b_lo) + e(a_lo, b_hi))


def _mlp_sizes(cfg: dict) -> tuple[tuple, tuple]:
    n = cfg["n_tables"] + 1
    top_in = cfg["embed_dim"] + n * (n - 1) // 2
    bot = (cfg["n_dense"],) + tuple(cfg["bot_mlp"])
    if bot[-1] != cfg["embed_dim"]:
        bot = bot + (cfg["embed_dim"],)
    return bot, (top_in,) + tuple(cfg["top_mlp"]) + (1,)


def _mlp_params(key, sizes: tuple) -> list:
    keys = jax.random.split(key, len(sizes) - 1)
    return [(jax.random.normal(k, (a, b), jnp.float32) * (1.0 / math.sqrt(a)),
             jnp.zeros((b,), jnp.float32))
            for k, a, b in zip(keys, sizes[:-1], sizes[1:], strict=True)]


def _mlp(params: list, x: jax.Array, precision: str) -> jax.Array:
    for i, (w, b) in enumerate(params):
        x = einsum("bi,io->bo", x, w, precision) + b
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


@functools.partial(jax.jit, static_argnames=("n_rows", "dim"))
def _table(key, n_rows: int, dim: int) -> jax.Array:
    scale = 1.0 / jnp.sqrt(jnp.float32(n_rows))
    return jax.random.uniform(key, (n_rows, dim), jnp.float32, -scale, scale)


@jax.jit
def _pool_bags(table: jax.Array, rows: jax.Array) -> jax.Array:
    return jnp.take(table, rows, axis=0).sum(axis=1)


@functools.partial(jax.jit, static_argnames="precision")
def _head(bot: list, top: list, dense: jax.Array, bags: jax.Array,
          precision: str) -> jax.Array:
    x = _mlp(bot, dense, precision)
    z = jnp.concatenate([x[:, None, :], bags], axis=1)
    dots = einsum("bid,bjd->bij", z, z, precision)
    iu, ju = np.triu_indices(z.shape[1], k=1)
    feat = jnp.concatenate([x, dots[:, iu, ju]], axis=1)
    return _mlp(top, feat, precision)[:, 0]


def _blocks(n: int, block: int):
    for lo in range(0, n, block):
        yield lo, min(n, lo + block)


def _pad_rows(x: np.ndarray, rows: int) -> np.ndarray:
    pad = rows - x.shape[0]
    return np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]) \
        if pad else x


def logits(cfg: dict, weight_seed: int, indices: np.ndarray,
           dense: np.ndarray, precision: str = "highest",
           block: int = 1024) -> np.ndarray:
    """Reference logits of requests ``indices`` (n, n_tables, lookups) of
    logical row ids with dense features ``dense`` (n, n_dense)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n, n_tables = indices.shape[0], cfg["n_tables"]
    block = min(block, n)
    keys = jax.random.split(jax.random.PRNGKey(weight_seed), n_tables + 2)
    bags = np.empty((n, n_tables, cfg["embed_dim"]), np.float32)
    for t in range(n_tables):
        table = _table(keys[t], cfg["n_rows"], cfg["embed_dim"])
        for lo, hi in _blocks(n, block):
            rows = jnp.asarray(_pad_rows(indices[lo:hi, t], block))
            bags[lo:hi, t] = np.asarray(_pool_bags(table, rows))[:hi - lo]
        del table
    bot_sizes, top_sizes = _mlp_sizes(cfg)
    bot = _mlp_params(keys[-2], bot_sizes)
    top = _mlp_params(keys[-1], top_sizes)
    out = np.empty(n, np.float32)
    for lo, hi in _blocks(n, block):
        out[lo:hi] = np.asarray(_head(
            bot, top, jnp.asarray(_pad_rows(dense[lo:hi], block)),
            jnp.asarray(_pad_rows(bags[lo:hi], block)),
            precision=precision))[:hi - lo]
    return out


def logit_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between served and reference logits, as a share of
    the reference logits' root mean square. Non-finite logits read
    ``NOT_A_NUMBER``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return NOT_A_NUMBER
    rms = float(np.sqrt(np.mean(want * want)))
    return float(np.max(np.abs(got - want))) / rms
