"""The comparison that decides ``correct``, at a size a test run holds.

Runs go through ``harness.run_cell`` on the CPU with each table cut to at
most 5,000 rows and a small pool; every width is the configuration's. The look
for a chip is skipped and nothing else: the served step, the batcher, the
real-clock window and the reference are the benchmark's own.
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench import config, harness
from chipbench.reference import logit_gap
from chipbench.traffic import make_pool

HERE = Path(__file__).resolve().parent
CELLS = [w["name"] for w in json.loads(
    (HERE.parents[1] / "BENCHMARK.json").read_text())["workloads"]]
ROWS = 5000


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def small_cell(name: str, rate: float = 400.0) -> harness.Cell:
    cell = harness.load_cell(name)
    return dataclasses.replace(
        cell, cfg=dict(cell.cfg, n_rows=[
            min(n, ROWS) for n in config.per_table(cell.cfg, "n_rows")]),
        traffic=dataclasses.replace(cell.traffic, pool=256, rate_rps=rate))


def run_small(name: str, seed: int = 2 ** 31 + 5, traced: bool = False
              ) -> dict:
    return harness.run_cell(small_cell(name), seed, 0.5, traced,
                            require_tpu=False, log=lambda *a, **k: None)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit_and_the_reference_passes(name):
    """The control (every matmul in three bf16 passes) reads above the
    limit on each seed; the reference against itself reads 0."""
    cell = small_cell(name)
    cfg = cell.cfg
    reference = config.reference(cfg)
    for seed in (1, 2, 3):
        idx, dense = make_pool(cell.traffic, config.per_table(cfg, "n_rows"),
                               config.per_table(cfg, "lookups"),
                               cfg["n_dense"], seed)
        want = reference.logits(cfg, seed, idx, dense)
        control = reference.logits(cfg, seed, idx, dense, "high3")
        assert logit_gap(want, want) == 0.0
        assert logit_gap(control, want) > cfg["logit_gap_limit"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result = run_small(name)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 200
    assert set(result["metrics"]) == {"p50_ms", "served_rps", "setup_s"}
    assert list(result)[-1] == "checks"


def _broken_step(kind: str, head):
    import repro.models.dlrm as dlrm

    @functools.partial(jax.jit, static_argnames="cfg")
    def serve_step(params, rank_ofs, batch, cfg):
        if kind == "control_high3":
            # the reference in the program's place, on the program's tables
            # and weights, every matmul in three bfloat16 passes
            bags = jnp.stack([
                jnp.take(table, jnp.take(rank_of, batch["indices"][:, t],
                                         axis=0), axis=0).sum(axis=1)
                for t, (table, rank_of) in enumerate(
                    zip(params["tables"], rank_ofs, strict=True))], axis=1)
            bot, top = ([(p["w"], p["b"]) for p in params[k]]
                        for k in ("bot", "top"))
            return head(bot, top, batch["dense"], bags, precision="high3")
        if kind == "translation_skipped":
            return dlrm.forward(params, batch, cfg)
        out = dlrm.forward(dlrm.add_remap(params, rank_ofs), batch, cfg)
        if kind == "answer_altered":
            return out.at[0].add(0.01 * jnp.sqrt(jnp.mean(out * out)))
        # half of the batch left out: odd rows get their neighbour's answer
        return out[jnp.arange(out.shape[0]) // 2 * 2]

    return serve_step


@pytest.mark.parametrize("kind", ("control_high3", "answer_altered",
                                  "half_batch_left_out",
                                  "translation_skipped"))
def test_broken_step_is_not_correct(kind, monkeypatch):
    from repro.launch import serve

    head = config.reference(small_cell(CELLS[0]).cfg)._head
    monkeypatch.setattr(serve, "serve_step", _broken_step(kind, head))
    result = run_small(CELLS[0])
    assert not result["correct"]
    gap = result["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_traced_run_reads_the_host_side_metrics():
    result = run_small(CELLS[0], traced=True)
    assert result["correct"]
    fill = result["metrics"]["batch_fill.rate"]["value"]
    assert 0 < fill <= 1
    assert result["metrics"]["p99_ms.rate"]["value"] > 0
    assert "window_s" in result["device"]


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and proc.stdout.strip() == ""


def test_weight_seed_keeps_large_seeds_apart():
    seeds = {harness.weight_seed(2 ** 31 + i) for i in range(100)}
    assert len(seeds) == 100 and all(0 <= s < 2 ** 31 for s in seeds)
    assert np.all(np.asarray(jax.random.PRNGKey(max(seeds))) >= 0)
