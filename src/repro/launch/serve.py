"""Serving driver — the RecFlash inference service on the serving subsystem.

Requests (one DLRM inference each) arrive on a Poisson or bursty open-loop
stream, wait in the ``RequestQueue``, are coalesced by the ``DynamicBatcher``
(max-batch / max-wait) and replayed through one ``Deployment`` — one policy
lane per NAND access policy, each lane ``--channels`` concurrent SLS
servers — so the identical stream is replayed against RecSSD / RM-SSD /
RecFlash and per-request p50/p95/p99 latency and throughput come out per
policy (DESIGN.md §3). Then the device half scores the RecFlash lane's
batches through the jitted DLRM forward (tables stored frequency-remapped
and lane-dense, logical ids translated via the rank_of hash table), padded
to a single shape that is compiled before the timed loop. Tables that do
not fit the device are an error, not a skipped half; ``--skip-compute``
opts out.

    PYTHONPATH=src python -m repro.launch.serve --requests 200 --batch 64
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.models.dlrm as dlrm
from repro.embedding.bag import pack_table
from repro.embedding.layout import PackedRanks, RemapSpec
from repro.flashsim.timeline import SERVING_POLICIES
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import mlp
from repro.serving import (BatcherConfig, Deployment, DeploymentConfig,
                           arch_model_config)

# deprecated alias — the single source is flashsim.timeline.SERVING_POLICIES
POLICY_NAMES = SERVING_POLICIES


@dataclasses.dataclass
class Scored:
    """What the device half scored, one row per request in dispatch order."""

    cfg: dlrm.DLRMConfig
    rids: np.ndarray        # (n,) request ids
    indices: np.ndarray     # (n, n_tables, lookups) logical row ids
    dense: np.ndarray       # (n, n_dense) dense features
    logits: np.ndarray      # (n,) device logits
    compile_s: float        # lower + compile of the one padded shape
    steady_s: float         # summed step time after compilation
    n_batches: int


def check_fits(need_bytes: int, device) -> None:
    """Raise unless ``device`` has ``need_bytes`` free. A backend that
    reports no memory statistics (the CPU) is not checked."""
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        return
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
    if need_bytes > free:
        raise RuntimeError(
            f"the model's tables and rank_of arrays need {need_bytes} B on "
            f"{device.device_kind} but {free} B of {stats['bytes_limit']} B "
            f"are free; cut the tables with --rows or pass --skip-compute")


@jax.jit
def _store(table: jax.Array, perm: jax.Array):
    """One table as it is served: its rows in rank order (``remap_table``),
    packed lane-dense where a 128-lane line holds several (``pack_table``).
    One program, so the rank-ordered table is never held beside its packed
    copy. ``perm`` is a permutation, so clipping changes no index; it
    spares the out-of-range fill, 0.5 MB of the program's code, which the
    device holds beside the tables."""
    return pack_table(jnp.take(table, perm, axis=0, mode="clip"))


def place_tables(cfg: dlrm.DLRMConfig, specs: list[RemapSpec], seed: int):
    """Initialise the model on the device and store every table
    frequency-remapped and lane-dense (``_store``), so the step gathers from
    the tables in place. Tables are stored one at a time and each original
    is released before the next, so peak memory stays near one copy. The
    rank_of hash tables are stored lane-dense too, as one ``PackedRanks``."""
    params = dlrm.init(jax.random.PRNGKey(seed), cfg)
    tables = params["tables"]
    for t, spec in enumerate(specs):
        tables[t] = jax.block_until_ready(
            _store(tables[t], jnp.asarray(spec.perm)))
    return params, PackedRanks.stack([s.rank_of for s in specs])


def row_buckets(rows: int) -> tuple[int, ...]:
    """The row counts the served step can run a batch of ``rows`` rows at:
    8, 16, 32, ... below ``rows`` (8 rows are one sublane tile), then
    ``rows``."""
    buckets = []
    k = 8
    while k < rows:
        buckets.append(k)
        k *= 2
    return (*buckets, rows)


def _live_rows(batch) -> jax.Array:
    """1 + the index of the last row whose ids or dense features differ
    from row 0's; the rows past it are copies of row 0. Dense features are
    compared bit for bit, so ``-0.0`` and NaN tell a row apart."""
    rows = batch["dense"].shape[0]
    dense = jax.lax.bitcast_convert_type(batch["dense"], jnp.int32)
    differs = (jnp.any(dense != dense[:1], axis=1)
               | jnp.any((batch["indices"] != batch["indices"][:1])
                         .reshape(rows, -1), axis=1))
    return jnp.max(jnp.where(differs, jnp.arange(1, rows + 1), 1))


def _on_rows(which: jax.Array, buckets: tuple[int, ...], fn) -> jax.Array:
    """``fn(k)``, the result for the first ``k = buckets[which]`` rows, under
    the scope ``rows_<k>``, with ``k`` chosen on the device (one
    ``lax.switch`` branch per bucket). It is filled to ``buckets[-1]`` rows
    with copies of its row 0: what ``fn`` gives the rows past ``k``, which
    are copies of row 0."""
    rows = buckets[-1]

    def branch(k: int):
        def run():
            with jax.named_scope(f"rows_{k}"):
                out = fn(k)
                return jnp.concatenate([out, jnp.broadcast_to(
                    out[:1], (rows - k, *out.shape[1:]))])
        return run

    if len(buckets) == 1:
        return branch(rows)()
    return jax.lax.switch(which, [branch(k) for k in buckets])


@functools.partial(jax.jit, static_argnames="cfg")
def serve_step(params, ranks: PackedRanks, batch, cfg: dlrm.DLRMConfig):
    """The served step: every table's logical ids translated to ranks in one
    gather (scope ``translate``), then the DLRM forward over the
    frequency-remapped tables, as ``dlrm.forward`` runs it on one device.
    The hash tables are an argument, so no table-sized constant is baked
    into the program.

    The translation and the SLS, whose work grows with the rows, run on
    the smallest of ``row_buckets`` that holds the batch's live rows
    (``_live_rows``); the rows past it are copies of row 0, as ``_pad``
    makes them, and take row 0's results. The bucket is chosen on the
    device, so one compiled step serves every fill. The MLPs and the
    interaction run on every row."""
    buckets = row_buckets(batch["dense"].shape[0])
    # translate and sls switch each inside its own scope, so every op of
    # the step, a conditional and its branches included, lies in one scope
    with jax.named_scope("translate"):
        which = jnp.sum(_live_rows(batch) > jnp.asarray(buckets[:-1]))
        idx = _on_rows(which, buckets,
                       lambda k: ranks.translate(batch["indices"][:k]))
    with jax.named_scope("sls"):
        bags = _on_rows(which, buckets, lambda k: jnp.stack([
            dlrm._bag(params, idx[:k, t, :], t, None, None)
            for t in range(cfg.n_tables)], axis=1))
    with jax.named_scope("mlp"):
        x = mlp(params["bot"], batch["dense"])
    with jax.named_scope("interact"):
        feat = dlrm.interact(x, bags, cfg.interaction)
    with jax.named_scope("mlp"):
        return mlp(params["top"], feat)[:, 0]


def _pad(x: np.ndarray, rows: int) -> np.ndarray:
    """Pad to ``rows`` by replicating row 0: the rows ``serve_step`` skips
    (``_live_rows``)."""
    pad = rows - x.shape[0]
    return np.concatenate([x, np.repeat(x[:1], pad, axis=0)]) if pad else x


def score_batches(batches, params, cfg, rank_ofs, dense_all,
                  max_batch: int) -> Scored:
    """Device half: jitted forward over the lane's batches, one compiled shape.

    Batches are padded to ``max_batch`` rows (row 0 replicated) so every
    dispatch runs the one program compiled before the timed loop; only real
    rows are scored.
    """
    shape = {"dense": jax.ShapeDtypeStruct((max_batch, cfg.n_dense),
                                           jnp.float32),
             "indices": jax.ShapeDtypeStruct(
                 (max_batch, cfg.n_tables, cfg.lookups), jnp.int32)}
    t0 = time.perf_counter()
    step = serve_step.lower(params, rank_ofs, shape, cfg=cfg).compile()
    compile_s = time.perf_counter() - t0

    rids, idxs, denses, logits = [], [], [], []
    steady_s = 0.0
    for b in batches:
        rid = np.array([r.rid for r in b.requests])
        idx = np.stack([r.rows.reshape(cfg.n_tables, cfg.lookups)
                        for r in b.requests])
        dense = dense_all[rid]
        batch = {"dense": jnp.asarray(_pad(dense, max_batch), jnp.float32),
                 "indices": jnp.asarray(_pad(idx, max_batch), jnp.int32)}
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(params, rank_ofs, batch))
        steady_s += time.perf_counter() - t0
        rids.append(rid)
        idxs.append(idx)
        denses.append(dense)
        logits.append(np.asarray(out[:len(rid)]))
    return Scored(cfg=cfg, rids=np.concatenate(rids),
                  indices=np.concatenate(idxs), dense=np.concatenate(denses),
                  logits=np.concatenate(logits), compile_s=compile_s,
                  steady_s=steady_s, n_batches=len(batches))


def main(argv: list[str] | None = None) -> Scored | None:
    """Run the driver; return what the device half scored (None with
    ``--skip-compute``)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=50,
                    help="number of inference requests in the stream")
    ap.add_argument("--arch", default="dlrm_small",
                    help="registry arch for shapes (dlrm_small, dlrm_rm2, "
                         "dlrm_mlperf, rmc1/2/3)")
    ap.add_argument("--rows", type=int, default=None,
                    help="override rows per table (cuts a full-size arch "
                         "down, e.g. for a CPU run)")
    ap.add_argument("--batch", type=int, default=64,
                    help="dynamic batcher max batch size (requests)")
    ap.add_argument("--max-wait-us", type=float, default=1000.0,
                    help="batcher max-wait budget for the oldest request")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="mean arrival rate, requests/sec (simulated)")
    ap.add_argument("--arrival", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--part", choices=("SLC", "TLC", "QLC"), default="TLC")
    ap.add_argument("--channels", type=int, default=1,
                    help="concurrent SLS servers per policy lane")
    ap.add_argument("--k", type=float, default=0.0,
                    help="trace locality knob (0 = most local)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-compute", action="store_true",
                    help="storage-side simulation only (no jit forward)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # --- the deployment: one declarative config, one facade ---------------
    dep_cfg = DeploymentConfig.from_arch(
        args.arch, part=args.part, n_rows=args.rows, k=args.k,
        seed=args.seed, n_channels=args.channels,
        batcher=BatcherConfig(max_batch=args.batch,
                              max_wait_us=args.max_wait_us))
    dep = Deployment(dep_cfg)
    cfg = arch_model_config(dep_cfg)
    specs = [RemapSpec.from_counts(s.counts) for s in dep.stats]

    # --- storage half: replay the stream against every policy -------------
    requests = dep.stream(args.requests, args.rate, arrival=args.arrival)
    t0 = time.time()
    traces = dep.run_stream(requests)
    t_sim = time.time() - t0

    # --- compute half: score the RecFlash lane's batches on the device ----
    scored = None
    if not args.skip_compute:
        device = jax.devices()[0]
        check_fits(sum(n * (cfg.embed_dim + 1) * 4 for n in cfg.n_rows),
                   device)
        params, rank_ofs = place_tables(cfg, specs, args.seed)
        dense_all = np.random.default_rng(args.seed * 7919).normal(
            size=(args.requests, cfg.n_dense)).astype(np.float32)
        scored = score_batches(traces["recflash"].batches, params, cfg,
                               rank_ofs, dense_all, args.batch)
        print(f"scored {len(scored.rids)} requests on {device.platform} "
              f"({device.device_kind}): compile {scored.compile_s:.2f}s, "
              f"steady {1e3 * scored.steady_s / max(1, scored.n_batches):.2f}"
              f" ms/batch over {scored.n_batches} batches")

    # --- report -----------------------------------------------------------
    print(f"\n{args.arrival} arrivals @ {args.rate:.0f} req/s, "
          f"batcher <= {args.batch} reqs / {args.max_wait_us:.0f} us wait, "
          f"{args.part} part, {args.channels} channel(s)/lane  "
          f"(simulated in {t_sim:.2f}s wall):\n")
    for pol, report in dep.report().items():
        print("  " + report.row())
    r_flash = traces["recflash"].report
    r_rmssd = traces["rmssd"].report
    if r_rmssd.p99_us > 0:
        print(f"\nrecflash vs rmssd: "
              f"{1 - r_flash.p99_us / r_rmssd.p99_us:.1%} lower p99, "
              f"{r_flash.throughput_rps / max(r_rmssd.throughput_rps, 1e-9):.2f}x "
              f"throughput")
    return scored


if __name__ == "__main__":
    main()
