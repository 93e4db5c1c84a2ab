"""Mixture-of-Experts FFN: top-k routing + GShard blocked dispatch.

Dispatch is sort-based (no giant one-hot dispatch tensors): token-expert
assignments are sorted by expert id, scattered into per-expert capacity
slots (E, C, D), and fed through block-diagonal einsum GEMMs — the GShard
formulation. Shapes stay static, it jits cleanly, and the HLO FLOPs equal
the true grouped-GEMM cost (``lax.ragged_dot`` lowers densely on the CPU
dry-run backend and would inflate the compute roofline E_local-fold).
Overflowing assignments beyond an expert's capacity are dropped
(capacity_factor bounds the drop rate — GShard/Switch standard).

Expert parallelism (``moe_shard_map``) uses the *replicated-activation EP*
scheme: with Megatron-style TP the block input is already replicated across
the ``model`` axis, so each shard (a) computes identical routing, (b) selects
up to ``capacity`` assignments owned by its local experts, (c) runs its local
grouped GEMM, and (d) combines partial outputs with the TP ``psum`` that the
surrounding block needs anyway — no all_to_all, no extra collective volume.
This is the shard-level analogue of the paper's plane distribution: hot
(over-subscribed) experts must be spread across shards or one shard's
capacity clips while others idle (DESIGN.md §2.2).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, normal_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int = 0           # shared (always-on) experts, DeepSeek-style
    capacity_factor: float = 1.5
    norm_topk: bool = True      # renormalise top-k probs (Qwen3)
    router_bias: bool = False   # aux-loss-free bias (DeepSeek) — inference
    act: str = "swiglu"


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_expert
    p = {
        "router": normal_init(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_gate": normal_init(ks[1], (e, d, f), d ** -0.5, dtype),
        "w_up": normal_init(ks[2], (e, d, f), d ** -0.5, dtype),
        "w_down": normal_init(ks[3], (e, f, d), f ** -0.5, dtype),
    }
    if cfg.router_bias:
        p["router_b"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared:
        fs = f * cfg.n_shared
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(sk[0], d, fs, dtype),
            "w_up": dense_init(sk[1], d, fs, dtype),
            "w_down": dense_init(sk[2], fs, d, dtype),
        }
    return p


def _route(params, x2d, cfg: MoEConfig):
    """x2d (T, D) -> top-k (probs (T,k) f32, experts (T,k) i32)."""
    logits = (x2d.astype(jnp.float32) @ params["router"])
    scores = jax.nn.softmax(logits, axis=-1)
    sel = scores + params["router_b"] if "router_b" in params else scores
    top_p, top_e = jax.lax.top_k(sel, cfg.top_k)
    if "router_b" in params:   # bias picks experts; gate uses unbiased probs
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_e


def _blocked_ffn(xb, w_gate, w_up, w_down, act: str):
    """Block-diagonal expert FFN: xb (E, C, D) -> (E, C, D).

    GShard-style fixed-capacity dispatch. The einsum over the expert dim is
    block-diagonal — HLO FLOPs are 2*E*C*D*F per matmul, exactly the grouped-
    GEMM cost (``lax.ragged_dot`` would lower densely on CPU and inflate the
    compute roofline term E_local-fold; on TPU the einsum maps to one MXU
    pass per expert block)."""
    g = jnp.einsum("ecd,edf->ecf", xb, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xb, w_up)
    if act == "swiglu":
        h = jax.nn.silu(g) * u
    elif act == "squared_relu":
        r = jax.nn.relu(g + u)   # non-gated: fold both projections
        h = r * r
    else:
        raise ValueError(act)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _gshard_ffn(params, x2d, tok, le, probs, valid, e_local, cap_e,
                act: str):
    """Dispatch assignments into per-expert capacity slots, run the blocked
    FFN, and combine back to tokens.

    ``tok``/``le``/``probs``/``valid`` are flat assignment arrays (N,); rows
    with ``valid=False`` or overflowing an expert's ``cap_e`` slots are
    dropped (GShard capacity clipping). Returns (T, D) combined output.
    """
    d = x2d.shape[1]
    n = le.shape[0]
    # sort by (local) expert; invalid rows sink to the tail
    order = jnp.argsort(jnp.where(valid, le, e_local))
    le_s = jnp.where(valid[order], le[order], e_local - 1)
    tok_s, p_s, v_s = tok[order], probs[order], valid[order]
    group_sizes = jnp.bincount(jnp.where(v_s, le_s, e_local),
                               length=e_local + 1)[:e_local]
    start = jnp.cumsum(group_sizes) - group_sizes
    slot = jnp.arange(n) - start[le_s]          # rank within expert group
    ok = v_s & (slot >= 0) & (slot < cap_e)
    slot_c = jnp.clip(slot, 0, cap_e - 1)
    rows = x2d[tok_s] * ok[:, None]
    xb = jnp.zeros((e_local, cap_e, d), x2d.dtype).at[le_s, slot_c].add(rows)
    out_b = _blocked_ffn(xb, params["w_gate"], params["w_up"],
                         params["w_down"], act)
    out_rows = out_b[le_s, slot_c] * (p_s.astype(out_b.dtype)
                                      * ok)[:, None]
    return jnp.zeros((x2d.shape[0], d), out_b.dtype).at[tok_s].add(out_rows)


def _shared_ffn(p, x):
    h = jax.nn.silu(x @ p["w_gate"]["w"]) * (x @ p["w_up"]["w"])
    return h @ p["w_down"]["w"]


def _cap_per_expert(cfg: MoEConfig, tokens: int) -> int:
    return max(4, int(cfg.capacity_factor * tokens * cfg.top_k
                      / cfg.n_experts))


def moe_ffn(params, x, cfg: MoEConfig):
    """Single-shard (or fully replicated experts) MoE FFN. x (..., D)."""
    shape = x.shape
    x2d = x.reshape(-1, cfg.d_model)
    t = x2d.shape[0]
    top_p, top_e = _route(params, x2d, cfg)

    flat_e = top_e.reshape(-1)                       # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), cfg.top_k)    # token of each assignment
    flat_p = top_p.reshape(-1)
    y = _gshard_ffn(params, x2d, flat_t, flat_e, flat_p,
                    jnp.ones_like(flat_e, bool), cfg.n_experts,
                    _cap_per_expert(cfg, t), cfg.act)
    if cfg.n_shared:
        y = y + _shared_ffn(params["shared"], x2d)
    return y.astype(x.dtype).reshape(shape)


def moe_ffn_sharded(params, x, cfg: MoEConfig, axis_name: str = "model"):
    """Replicated-activation EP: call inside shard_map over ``axis_name``.

    ``params['w_gate'|'w_up'|'w_down']`` hold only the local expert slice
    (E_local, ...); routing params are replicated. ``x`` (..., D) is the
    TP-replicated block input. Each shard keeps the assignments owned by its
    local experts (others are some other shard's job), runs the blocked
    per-expert FFN, and the psum over ``axis_name`` — the TP reduction the
    surrounding block needs anyway — completes the combine. No all_to_all,
    no extra collective volume.
    """
    shard = jax.lax.axis_index(axis_name)
    e_local = params["w_gate"].shape[0]
    shape = x.shape
    x2d = x.reshape(-1, cfg.d_model)
    t = x2d.shape[0]
    top_p, top_e = _route(params, x2d, cfg)

    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), cfg.top_k)
    flat_p = top_p.reshape(-1)
    local = (flat_e // e_local) == shard
    y = _gshard_ffn(params, x2d, flat_t, flat_e % e_local, flat_p, local,
                    e_local, _cap_per_expert(cfg, t), cfg.act)
    if cfg.n_shared:
        # shared expert is TP-sharded over d_ff: local slice computes a
        # partial product completed by the same psum.
        y = y + _shared_ffn(params["shared"], x2d)
    return jax.lax.psum(y, axis_name).astype(x.dtype).reshape(shape)


def moe_ffn_2d(params, x, cfg: MoEConfig, model_axis: str = "model",
               data_axis: str = "data", batch_axes=("data",),
               token_chunk: int | None = None):
    """Weight-stationary 2D expert sharding for serving (decode path).

    The FSDP layout used for training gathers every layer's expert weights
    across ``data`` — fine when a step amortises it over 1M tokens,
    pathological at decode (measured: 157 GB of wire per deepseek-v3 decode
    step). Serving reshards the weights instead: experts over ``model``,
    each expert's FFN dim F over ``data`` (so a 671B model still fits at
    ~7 GB/chip), and the *activations* move — which at decode is a few
    hundred KB:

      1. all-gather the (tokens, d_model) block across the batch axes;
      2. every shard routes identically, selects assignments owned by its
         local experts, and runs its (E_local, D, F_local) grouped GEMM;
      3. one psum over (data, model) completes both the F partial sums and
         the cross-expert combine;
      4. each shard slices its own batch rows back out.

    The shared expert's F dim is sharded over (data x model) jointly so the
    same psum finishes it without overcounting.

    ``token_chunk`` bounds the gathered activation block for prefill-sized
    token counts: local rows are processed in chunks of that size (scan), so
    the gathered block is (token_chunk x n_batch_shards, d_model) instead of
    the full 15 GB a 1M-token deepseek prefill would otherwise gather.
    """
    d = cfg.d_model
    shape = x.shape
    x2d = x.reshape(-1, d)
    rows = x2d.shape[0]
    if token_chunk and rows > token_chunk and rows % token_chunk == 0:
        nc = rows // token_chunk
        xc = x2d.reshape(nc, token_chunk, d)

        def body(_, chunk):
            return None, _moe_2d_block(params, chunk, cfg, model_axis,
                                       data_axis, batch_axes)

        _, yc = jax.lax.scan(body, None, xc)
        return yc.reshape(shape)
    return _moe_2d_block(params, x2d, cfg, model_axis, data_axis,
                         batch_axes).reshape(shape)


def _moe_2d_block(params, x2d, cfg: MoEConfig, model_axis, data_axis,
                  batch_axes):
    shard = jax.lax.axis_index(model_axis)
    e_local = params["w_gate"].shape[0]
    rows = x2d.shape[0]
    x_full = jax.lax.all_gather(x2d, batch_axes, axis=0, tiled=True)
    t = x_full.shape[0]
    top_p, top_e = _route(params, x_full, cfg)

    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), cfg.top_k)
    flat_p = top_p.reshape(-1)
    local = (flat_e // e_local) == shard
    y = _gshard_ffn(params, x_full, flat_t, flat_e % e_local, flat_p, local,
                    e_local, _cap_per_expert(cfg, t), cfg.act)
    if cfg.n_shared:
        y = y + _shared_ffn(params["shared"], x_full)
    y = jax.lax.psum(y, (data_axis, model_axis)).astype(x2d.dtype)
    # slice this shard's batch rows back out (batch-major gather order)
    idx = 0
    for ax in batch_axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return jax.lax.dynamic_slice_in_dim(y, idx * rows, rows, axis=0)


def load_balance_loss(params, x2d, cfg: MoEConfig):
    """Switch-style aux loss: E * sum_e f_e * p_e (f = fraction routed)."""
    logits = x2d.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    _, top_e = jax.lax.top_k(probs, cfg.top_k)
    f = jnp.mean(jax.nn.one_hot(top_e, cfg.n_experts).sum(-2), axis=0)
    p = probs.mean(0)
    return cfg.n_experts * jnp.sum(f * p / cfg.top_k)
