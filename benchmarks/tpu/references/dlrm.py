"""Plain DLRM reference for the chip benchmark, and the work its step requires.

The reference imports nothing of the program. It makes the model's logical
weights from the weight seed with the same ``jax.random`` draws the program's
initialisation makes (threefry keys split ``n_tables + 2`` ways; table ``t``
uniform in +-1/sqrt(rows_t), drawn in the file's table ``dtype``; MLP weights
float32 normal / sqrt(fan_in), zero biases), and scores requests by their
logical row ids on the un-remapped tables, in float32:

    bags[t]  = sum over table t's lookups of table_t[row]   (SLS)
    x        = bottom MLP(dense), ReLU between layers
    z        = [x, bags[0], ..., bags[T-1]]
    features = [x, z_i . z_j for i < j]                      (dot interaction)
    logit    = top MLP(features)

Each table has its own vocabulary and pooling (``config.per_table``). It
runs table by table and in blocks of rows, after the program's state has
been freed, so one table and one block are on the device at a time.

The work counts are kept here, beside the mathematics they count, so that a
change to the program cannot move them. The operation count is a copy of
``repro.models.dlrm.DLRMConfig.flops_per_sample``, less three counts that
are not required work. The byte count is what any implementation of the
step has to move between HBM and the core, once per step:

* each distinct embedding row that the batch touches, once (nothing stays
  in VMEM from one step to the next), at ``embed_dim`` x the table dtype;
* the MLP weights and biases;
* the dense features and indices of the real rows in, their logits out.

It leaves out the ``rank_of`` reads (a layout choice, not work the model
requires) and the padded rows. So no implementation can read above 100%.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from chipbench.config import BenchError, by_table, per_table, table_dtype
from chipbench.reference import PRECISIONS, einsum
from chipbench.yardstick import distinct_rows

# bytes of a float32 weight, dense feature or logit, and of an int32 id
WORD = 4


# ------------------------------------------------------------------ sizes
def top_in(cfg: dict) -> int:
    if cfg["interaction"] != "dot":
        raise BenchError(f"{cfg['name']}: the DLRM reference computes the dot "
                         f"interaction, not {cfg['interaction']!r}")
    n = cfg["n_tables"] + 1
    return cfg["embed_dim"] + n * (n - 1) // 2


def mlp_sizes(cfg: dict) -> tuple[tuple, tuple]:
    """Layer widths of the bottom and the top MLP, inputs first."""
    bot = (cfg["n_dense"],) + tuple(cfg["bot_mlp"])
    if bot[-1] != cfg["embed_dim"]:
        bot = bot + (cfg["embed_dim"],)
    return bot, (top_in(cfg),) + tuple(cfg["top_mlp"]) + (1,)


# ------------------------------------------------------------ the forward
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


@functools.partial(jax.jit, static_argnames=("n_rows", "dim", "dtype"))
def _table(key, n_rows: int, dim: int, dtype: str) -> jax.Array:
    scale = 1.0 / jnp.sqrt(jnp.float32(n_rows))
    return jax.random.uniform(key, (n_rows, dim), dtype, -scale, scale)


@jax.jit
def _pool_bags(table: jax.Array, rows: jax.Array) -> jax.Array:
    return jnp.take(table, rows, axis=0).astype(jnp.float32).sum(axis=1)


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
    """Reference logits of requests ``indices`` of logical row ids, in
    either layout of ``config``, with dense features ``dense``
    (n, n_dense)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    n_rows = per_table(cfg, "n_rows")
    dtype = table_dtype(cfg).name
    bot_sizes, top_sizes = mlp_sizes(cfg)
    n, n_tables = indices.shape[0], cfg["n_tables"]
    block = min(block, n)
    keys = jax.random.split(jax.random.PRNGKey(weight_seed), n_tables + 2)
    bags = np.empty((n, n_tables, cfg["embed_dim"]), np.float32)
    for t, ids in enumerate(by_table(indices, per_table(cfg, "lookups"))):
        table = _table(keys[t], n_rows[t], cfg["embed_dim"], dtype)
        for lo, hi in _blocks(n, block):
            rows = jnp.asarray(_pad_rows(ids[lo:hi], block))
            bags[lo:hi, t] = np.asarray(_pool_bags(table, rows))[:hi - lo]
        del table
    bot = _mlp_params(keys[-2], bot_sizes)
    top = _mlp_params(keys[-1], top_sizes)
    out = np.empty(n, np.float32)
    for lo, hi in _blocks(n, block):
        out[lo:hi] = np.asarray(_head(
            bot, top, jnp.asarray(_pad_rows(dense[lo:hi], block)),
            jnp.asarray(_pad_rows(bags[lo:hi], block)),
            precision=precision))[:hi - lo]
    return out


# ------------------------------------------------------------ work counts
def flops_per_sample(cfg: dict) -> int:
    """Forward FLOPs one request requires: 2 x MACs of the MLP layers, 2 x
    ``embed_dim`` for each pair ``i < j`` of the dot interaction, and one
    add per pooled element of the SLS.

    The arithmetic of ``DLRMConfig.flops_per_sample``, less three counts
    that are not required work: a bottom layer ``embed_dim -> embed_dim``
    that the model does not have, the lower triangle and diagonal of the
    interaction, and a multiply per pooled element.
    """
    bot, top = mlp_sizes(cfg)
    f = sum(2 * a * b for sizes in (bot, top)
            for a, b in zip(sizes[:-1], sizes[1:], strict=True))
    n = cfg["n_tables"] + 1
    f += n * (n - 1) * cfg["embed_dim"]
    f += sum(per_table(cfg, "lookups")) * cfg["embed_dim"]
    return f


def mlp_weight_bytes(cfg: dict) -> int:
    return sum(WORD * (a * b + b) for sizes in mlp_sizes(cfg)
               for a, b in zip(sizes[:-1], sizes[1:], strict=True))


def _row_bytes(cfg: dict, indices: np.ndarray) -> int:
    """Each distinct (table, row) of ``indices``, read once."""
    return (distinct_rows(indices, per_table(cfg, "lookups"))
            * cfg["embed_dim"] * table_dtype(cfg).itemsize)


def step_bytes(cfg: dict, indices: np.ndarray) -> int:
    """Bytes one step must move for the real rows ``indices``."""
    n = indices.shape[0]
    return (_row_bytes(cfg, indices)
            + mlp_weight_bytes(cfg)
            + n * cfg["n_dense"] * WORD
            + indices.size * WORD
            + n * WORD)


def sls_work(cfg: dict, indices: np.ndarray) -> tuple[int, int]:
    """``(flops, bytes)`` the SLS of the real rows ``indices`` requires: one
    add per pooled element, and each distinct (table, row) read once. No
    ``rank_of`` reads and no padded rows, so no implementation of the SLS
    (``jnp.take``, ``recflash_sls`` or a fused layout) can read above
    100%."""
    return indices.size * cfg["embed_dim"], _row_bytes(cfg, indices)
