"""Chip smoke test: the served DLRM path and its kernels, run on a TPU.

    python chip_smoke.py             # one chip: serve phase, kernel phase
    python chip_smoke.py --chips 4   # four chips: the row-sharded forward

Serve phase. ``repro.launch.serve`` at dlrm_rm2 width (26 tables x 1M rows
x 64, 80 lookups per table, no rows cut) replays 256 Poisson requests at
batch 64 and scores the RecFlash lane's batches on the device. The logits
of a sample of scored requests are checked against a float32 reference on
the host's CPU device: plain ``dlrm.forward`` on the un-remapped tables,
rebuilt from the same seed, with logical ids. Both sides run their matmuls
at ``highest`` precision, so they differ only in summation order and the
bound is an f32 one.

Kernel phase. ``ops.recflash_sls`` at dlrm_rm2 widths (D=64, L=80, batch
64, hot tier from ``RemapSpec.from_counts`` on a 1M-row table) and
``ops.dot_interaction`` (T=27) must compile to a Mosaic kernel
(``tpu_custom_call`` in the compiled text) and match their oracles.

``--chips 4`` runs only the row-sharded forward on a 1 x 4 ("data",
"model") mesh: the dlrm_rm2 tables go from the host straight to their
shards (``P("model", None)``, ``rank_of`` as ``P("model")``), each device
must hold a quarter of every table, and the logits must match the same
CPU reference.

Every phase is fatal. The last line of standard output is the JSON result,
printed only when every phase passed. Without a TPU the script exits
nonzero before any phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "dlrm_rm2"
SEED = 0
N_REQUESTS = 256
BATCH = 64
N_CHECK = 64            # scored requests compared with the reference
# Logits: f32 on both sides, matmuls at highest precision; only the order
# of the 80-term bag sums and of the MLP reductions differs.
LOGIT_RTOL, LOGIT_ATOL = 1e-4, 1e-5
# Kernels: the same f32 terms summed in another order (80-row bags,
# 64-wide dots of unit-normal values, whose sums reach ~10).
KERNEL_RTOL, KERNEL_ATOL = 1e-5, 1e-4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def tpu_device():
    """The first device, which must be a TPU; there is no CPU fallback."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is on platform "
             f"{dev.platform!r}")
    return dev


def check_close(name: str, got, want, rtol: float, atol: float) -> None:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        fail(f"{name}: shape {got.shape}, reference {want.shape}")
    err = np.abs(got - want)
    print(f"[{name}] max abs err {err.max():.3e}, max rel err "
          f"{err.max() / np.abs(want).max():.3e} (relative to max |ref| "
          f"{np.abs(want).max():.3e}); bound |err| <= {atol} + {rtol} "
          f"x |ref|", flush=True)
    if not np.isfinite(got).all():
        fail(f"{name}: non-finite values")
    if (err > atol + rtol * np.abs(want)).any():
        fail(f"{name}: outside the bound")


def host_params(cfg, seed: int):
    """The un-remapped model, initialised from ``seed`` on the CPU device."""
    import jax
    import repro.models.dlrm as dlrm
    with jax.default_device(jax.devices("cpu")[0]):
        return dlrm.init(jax.random.PRNGKey(seed), cfg)


def reference_logits(cfg, params, batch):
    """Float32 ``dlrm.forward`` on the CPU device, logical ids, no remap."""
    import functools

    import jax
    import numpy as np
    import repro.models.dlrm as dlrm
    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(dlrm.forward, cfg=cfg))
        return np.asarray(fwd(params, batch))


def serve_phase(serve_argv: list[str], seed: int) -> None:
    import jax
    import numpy as np
    from repro.launch import serve

    with jax.default_matmul_precision("highest"):
        scored = serve.main(serve_argv)
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    cfg = scored.cfg
    n_req = int(serve_argv[serve_argv.index("--requests") + 1])
    if sorted(scored.rids.tolist()) != list(range(n_req)):
        fail(f"serve: scored {len(scored.rids)} of {n_req} requests")
    print(f"[serve] {len(scored.rids)} requests scored on {dev.device_kind}"
          f": {cfg.n_tables} tables x {cfg.n_rows[0]} rows x "
          f"{cfg.embed_dim}, {cfg.lookups} lookups per table; compile "
          f"{scored.compile_s:.3f} s; steady "
          f"{1e3 * scored.steady_s / scored.n_batches:.3f} ms/batch over "
          f"{scored.n_batches} batches; peak_bytes_in_use {peak}",
          flush=True)
    pick = np.random.default_rng(seed).choice(
        len(scored.rids), size=min(N_CHECK, len(scored.rids)), replace=False)
    ref = reference_logits(cfg, host_params(cfg, seed),
                           {"dense": scored.dense[pick],
                            "indices": scored.indices[pick]})
    check_close("serve logits", scored.logits[pick], ref, LOGIT_RTOL,
                LOGIT_ATOL)


def _compiled_kernel(fn, *args) -> None:
    if "tpu_custom_call" not in fn.lower(*args).compile().as_text():
        fail(f"{fn.__name__}: no tpu_custom_call in the compiled program")


def kernel_phase(n_rows: int, dim: int, lookups: int, n_vectors: int,
                 seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.freq import AccessStats
    from repro.data.tracegen import generate_sls_batch
    from repro.embedding.layout import RemapSpec, remap_table
    from repro.kernels import ops

    _, sample = generate_sls_batch(1, n_rows, lookups, 512, k=0.0,
                                   seed=seed + 1)
    spec = RemapSpec.from_counts(AccessStats.from_trace(sample,
                                                        n_rows).counts)
    k_tab, k_z = jax.random.split(jax.random.PRNGKey(seed))
    stored = remap_table(
        jax.random.normal(k_tab, (n_rows, dim), jnp.float32), spec)
    hot, cold = stored[:spec.hot_size], stored[spec.hot_size:]
    _, rows = generate_sls_batch(1, n_rows, lookups, BATCH, k=0.0,
                                 seed=seed + 2)
    ranks = jnp.asarray(spec.rank_of[rows.reshape(BATCH, lookups)],
                        jnp.int32)
    _compiled_kernel(ops.recflash_sls, hot, cold, ranks)
    out = ops.recflash_sls(hot, cold, ranks)
    print(f"[recflash_sls] compiled to tpu_custom_call; hot tier "
          f"{spec.hot_size} of {n_rows} rows x {dim}, {lookups} lookups x "
          f"batch {BATCH}, hot hits {float((ranks < spec.hot_size).mean()):.3f}",
          flush=True)
    check_close("recflash_sls", out, ops.sls_ref(hot, cold, ranks),
                KERNEL_RTOL, KERNEL_ATOL)

    z = jax.random.normal(k_z, (BATCH, n_vectors, dim), jnp.float32)
    iu, ju = np.triu_indices(n_vectors, k=1)
    with jax.default_matmul_precision("highest"):
        _compiled_kernel(ops.dot_interaction, z)
        out = ops.dot_interaction(z)
        ref = ops.dot_ref(z)[:, iu, ju]
    print(f"[dot_interaction] compiled to tpu_custom_call; T={n_vectors}, "
          f"D={dim}, batch {BATCH}", flush=True)
    check_close("dot_interaction", out, ref, KERNEL_RTOL, KERNEL_ATOL)


def sharded_phase(cfg, seed: int, n_model: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import repro.models.dlrm as dlrm
    from repro.core.freq import AccessStats
    from repro.data.tracegen import generate_sls_batch
    from repro.embedding.layout import RemapSpec
    from repro.launch.mesh import make_local_mesh

    if len(jax.devices()) != n_model:
        fail(f"sharded: needs {n_model} devices, found {len(jax.devices())}")
    mesh = make_local_mesh(1, n_model)
    n_rows = cfg.n_rows[0]
    ref_params = host_params(cfg, seed)
    # the offline phase: a sampled sweep ranks each table's rows, and the
    # hot ranks are striped over the model shards (PD)
    tb, sample = generate_sls_batch(cfg.n_tables, n_rows, cfg.lookups, 512,
                                    k=0.0, seed=seed + 1)
    specs = [RemapSpec.from_counts(
        AccessStats.from_trace(sample[tb == t], n_rows).counts,
        n_shards=n_model) for t in range(cfg.n_tables)]

    t0 = time.perf_counter()
    row_shards = NamedSharding(mesh, P("model", None))
    id_shards = NamedSharding(mesh, P("model"))
    replicated = NamedSharding(mesh, P())
    tables = [jax.device_put(np.asarray(tbl)[s.perm], row_shards)
              for tbl, s in zip(ref_params["tables"], specs, strict=True)]
    params = {"tables": tables,
              "rank_of": [jax.device_put(s.rank_of.astype(np.int32),
                                         id_shards) for s in specs],
              "bot": jax.device_put(ref_params["bot"], replicated),
              "top": jax.device_put(ref_params["top"], replicated)}
    jax.block_until_ready(params)
    placed_s = time.perf_counter() - t0

    quarter = (n_rows // n_model, cfg.embed_dim)
    for t, tbl in enumerate(tables):
        shards = tbl.addressable_shards
        if (len({s.device for s in shards}) != n_model
                or any(s.data.shape != quarter for s in shards)):
            fail(f"sharded: table {t} shards "
                 f"{[(str(s.device), s.data.shape) for s in shards]}")
    print(f"[sharded] each of {n_model} devices holds {quarter[0]} x "
          f"{quarter[1]} of each of {cfg.n_tables} tables (placed in "
          f"{placed_s:.3f} s)", flush=True)

    _, rows = generate_sls_batch(cfg.n_tables, n_rows, cfg.lookups, BATCH,
                                 k=0.0, seed=seed + 2)
    batch = {"dense": np.random.default_rng(seed).normal(
                 size=(BATCH, cfg.n_dense)).astype(np.float32),
             "indices": rows.reshape(BATCH, cfg.n_tables,
                                     cfg.lookups).astype(np.int32)}
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, b: dlrm.forward(p, b, cfg, mesh=mesh))
        dev_batch = jax.device_put(batch, replicated)
        t0 = time.perf_counter()
        step = fwd.lower(params, dev_batch).compile()
        compile_s = time.perf_counter() - t0
        logits = np.asarray(step(params, dev_batch))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"[sharded] forward on a 1 x {n_model} mesh: compile "
          f"{compile_s:.3f} s; peak_bytes_in_use per device {peaks}",
          flush=True)
    check_close("sharded logits", logits,
                reference_logits(cfg, ref_params, batch), LOGIT_RTOL,
                LOGIT_ATOL)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded forward on 4 chips")
    args = ap.parse_args(argv)
    # the reference runs on the host's CPU device, next to the TPU
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import DeploymentConfig, arch_model_config

    dev = tpu_device()
    print(f"[device] {dev.device_kind} x {len(jax.devices())}; compile "
          f"cache {enable_compile_cache()}", flush=True)
    cfg = arch_model_config(DeploymentConfig.from_arch(ARCH))
    if args.chips == 4:
        phases = [("sharded", lambda: sharded_phase(cfg, SEED, n_model=4))]
    else:
        phases = [("serve", lambda: serve_phase(
                      ["--arch", ARCH, "--requests", str(N_REQUESTS),
                       "--batch", str(BATCH), "--seed", str(SEED)], SEED)),
                  ("kernels", lambda: kernel_phase(
                      cfg.n_rows[0], cfg.embed_dim, cfg.lookups,
                      cfg.n_vectors, SEED))]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"[{name}] phase passed in {time.perf_counter() - t0:.1f} s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
