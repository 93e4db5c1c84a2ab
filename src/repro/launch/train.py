"""End-to-end training driver.

Trains any registered arch end-to-end on synthetic data with the
fault-tolerant TrainLoop (checkpoint/restart, straggler hook) on whatever
devices exist. This is the single-host path used by the examples and CI;
the production meshes are exercised by ``dryrun.py`` (no real 512-chip
allocation exists here).

    PYTHONPATH=src python -m repro.launch.train --model dlrm \
        --steps 200 --batch 256 --ckpt-dir /tmp/ckpt

For multi-host DP deployments, ``repro.distributed.compression`` provides
the error-feedback int8 all-reduce (validated in tests/test_multidev.py);
wire it into a shard_map'd step the way the tests do.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import LoopConfig, TrainLoop


def small_dlrm(n_rows=50_000):
    from repro.models.dlrm import DLRMConfig
    return DLRMConfig(
        name="dlrm-small", n_tables=8, n_dense=13, embed_dim=64,
        n_rows=(n_rows,) * 8, lookups=20, bot_mlp=(256, 128, 64),
        top_mlp=(256, 128))


def _dlrm_pipeline(args, remap: bool):
    """Returns (params, opt, loss_fn, batch_fn, stats) for DLRM training."""
    import repro.models.dlrm as dlrm
    from repro.core.freq import AccessStats
    from repro.data.tracegen import generate_sls_batch
    from repro.embedding.layout import RemapSpec, remap_table

    cfg = small_dlrm()
    params = dlrm.init(jax.random.PRNGKey(args.seed), cfg)

    # offline phase (paper Fig. 8): sampled sweep -> AF remap of the tables
    rank_ofs = None
    if remap:
        tb, rows = generate_sls_batch(cfg.n_tables, cfg.n_rows[0],
                                      cfg.lookups, 512, k=0.0,
                                      seed=args.seed + 1)
        specs = []
        for t in range(cfg.n_tables):
            counts = AccessStats.from_trace(rows[tb == t],
                                            cfg.n_rows[0]).counts
            specs.append(RemapSpec.from_counts(counts))
        params["tables"] = [remap_table(tbl, s)
                            for tbl, s in zip(params["tables"], specs, strict=True)]
        rank_ofs = [jnp.asarray(s.rank_of) for s in specs]

    opt = optim.partitioned(
        lambda ks: "table" if "tables" in ks else "dense",
        {"table": optim.adagrad(args.lr_table, rowwise=True),
         "dense": optim.adamw(args.lr)})

    def batch_fn(step):
        rng = np.random.default_rng(args.seed * 100_000 + step)
        tb, rows = generate_sls_batch(cfg.n_tables, cfg.n_rows[0],
                                      cfg.lookups, args.batch, k=0.0,
                                      seed=step)
        idx = rows.reshape(args.batch, cfg.n_tables, cfg.lookups)
        dense = rng.normal(size=(args.batch, cfg.n_dense)) \
            .astype(np.float32)
        # synthetic CTR: clicks correlate with dense feature 0
        labels = (dense[:, 0] + rng.normal(scale=0.5, size=args.batch)
                  > 0.5).astype(np.float32)
        return {"dense": jnp.asarray(dense),
                "indices": jnp.asarray(idx, jnp.int32),
                "labels": jnp.asarray(labels)}

    def loss_fn(p, batch):
        pp = dlrm.add_remap(p, rank_ofs) if rank_ofs is not None else p
        return dlrm.loss(pp, batch, cfg)

    return params, opt, loss_fn, batch_fn


def _lm_pipeline(args):
    from repro.models import lm
    cfg = lm.LMConfig(name="lm-100m", n_layers=8, d_model=512, n_heads=8,
                      n_kv_heads=4, d_ff=2048, vocab=32_000, qk_norm=True,
                      tie_embeddings=True, remat=False, q_chunk=128,
                      kv_chunk=128)
    params = lm.init(jax.random.PRNGKey(args.seed), cfg)
    opt = optim.adamw(args.lr, weight_decay=0.1)

    def batch_fn(step):
        rng = np.random.default_rng(step)
        seq = args.seq_len
        # synthetic LM data: markov-ish token stream
        toks = rng.integers(0, cfg.vocab, (args.batch, seq + 1))
        return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                "targets": jnp.asarray(toks[:, 1:], jnp.int32)}

    def loss_fn(p, batch):
        return lm.train_loss(p, batch, cfg)

    return params, opt, loss_fn, batch_fn


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=("dlrm", "lm"), default="dlrm")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lr-table", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-remap", action="store_true",
                    help="disable the RecFlash AF table remap (baseline)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    if args.model == "dlrm":
        params, opt, loss_fn, batch_fn = _dlrm_pipeline(
            args, remap=not args.no_remap)
    else:
        params, opt, loss_fn, batch_fn = _lm_pipeline(args)

    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"model={args.model} params={n_params/1e6:.1f}M "
          f"devices={len(jax.devices())}")

    @jax.jit
    def step_fn(state, batch):
        params, opt_state, _ = state
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch))(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    losses = []
    t_start = time.time()

    def metrics_hook(step, state):
        losses.append(float(state[2]))
        if (step + 1) % args.log_every == 0:
            dt = time.time() - t_start
            print(f"step {step + 1:5d}  loss {losses[-1]:.4f}  "
                  f"({dt / (step + 1):.3f}s/step)", flush=True)

    loop = TrainLoop(
        cfg=LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every),
        step_fn=step_fn, batch_fn=batch_fn,
        on_straggler=lambda s, dt, med: print(
            f"[straggler] step {s}: {dt:.2f}s vs median {med:.2f}s"))

    state = (params, opt.init(params), jnp.zeros(()))
    orig_attempt = loop._attempt

    def attempt_and_log(state, batch):
        out = orig_attempt(state, batch)
        metrics_hook(len(losses), out)
        return out

    loop._attempt = attempt_and_log
    state = loop.run(state)
    print(f"final loss {float(state[2]):.4f} after {args.steps} steps "
          f"in {time.time() - t_start:.1f}s")
    if len(losses) > 20:
        first = np.mean(losses[:10])
        last = np.mean(losses[-10:])
        print(f"loss first10={first:.4f} last10={last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
