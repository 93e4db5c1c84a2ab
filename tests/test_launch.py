"""Launcher integration: train/serve drivers end-to-end (subprocess) and
cell-plan construction for every (arch x shape) on the production mesh
(eval_shape only — the full lower+compile sweep is dryrun.py's job)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
# the entry points turn on the persistent compilation cache; tests keep it off
ENV = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


def run(args, timeout=420, env=ENV):
    r = subprocess.run([sys.executable] + args, env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-3000:]}\n" \
                              f"STDERR:\n{r.stderr[-3000:]}"
    return r.stdout


class TestDrivers:
    def test_train_driver_improves_loss_and_resumes(self, tmp_path):
        out = run(["-m", "repro.launch.train",
                   "--steps", "30", "--batch", "64", "--ckpt-every", "10",
                   "--ckpt-dir", str(tmp_path)])
        assert "improved" in out or "final loss" in out
        # resume: a second invocation restarts from the checkpoint
        out2 = run(["-m", "repro.launch.train",
                    "--steps", "40", "--batch", "64", "--ckpt-every", "10",
                    "--ckpt-dir", str(tmp_path)])
        assert "final loss" in out2

    def test_serve_driver_reports_policy_gap(self):
        out = run(["-m", "repro.launch.serve", "--requests", "4",
                   "--batch", "16"])
        # per-policy tail-latency report from the serving stack
        for pol in ("recssd", "rmssd", "recflash"):
            assert f"\n  {pol}" in out
        assert "p50" in out and "p99" in out
        assert "recflash vs rmssd" in out
        # the RecFlash policy must win on the simulated device
        pct = float(out.split("recflash vs rmssd:")[1].split("%")[0])
        assert pct > 0

    def test_serve_main_scores_every_request_on_the_device(self,
                                                            monkeypatch):
        """In-process: main takes argv and returns what it scored; every
        request of the RecFlash lane is scored once, after the compile."""
        import numpy as np

        from repro.launch import serve
        monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
        scored = serve.main(["--requests", "12", "--batch", "4",
                             "--rows", "2000"])
        assert sorted(scored.rids.tolist()) == list(range(12))
        assert scored.logits.shape == (12,)
        assert np.isfinite(scored.logits).all()
        assert scored.indices.shape == (12, scored.cfg.n_tables,
                                        scored.cfg.lookups)
        assert scored.compile_s > 0 and scored.n_batches >= 3
        monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
        assert serve.main(["--requests", "4", "--skip-compute"]) is None

    def test_tables_that_do_not_fit_the_device_raise(self):
        from repro.launch.serve import check_fits

        class Chip:
            device_kind = "TPU test"

            def __init__(self, stats):
                self.stats = stats

            def memory_stats(self):
                return self.stats

        with pytest.raises(RuntimeError, match="2000 B .* 800 B of 1000 B"):
            check_fits(2000, Chip({"bytes_limit": 1000, "bytes_in_use": 200}))
        check_fits(800, Chip({"bytes_limit": 1000, "bytes_in_use": 200}))
        check_fits(2000, Chip(None))       # no statistics: not checked


REC_CELLS = ("train_batch", "serve_p99", "serve_bulk", "retrieval_cand")
GNN_CELLS = ("full_graph_sm", "minibatch_lg", "ogb_products", "molecule")
PLAN_CELLS = ([("graphsage-reddit", c) for c in GNN_CELLS]
              + [(a, c) for a in ("bert4rec", "din", "dlrm-mlperf",
                                  "dlrm-rm2", "rmc1", "rmc2", "rmc3")
                 for c in REC_CELLS])

# Builds every registered (arch x cell) plan under the 512-device mesh in
# one process and prints one tab-separated line per cell: "ok" or the error.
PLAN_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.configs.base import get_arch, list_archs
from repro.launch.mesh import make_production_mesh
mesh = make_production_mesh()
for name in list_archs():
    bundle = get_arch(name)
    for shape, step in bundle.steps.items():
        try:
            plan = step.make_fn(bundle, mesh, False)
            assert plan.fn is not None and plan.args, "empty plan"
            flat_args = jax.tree.leaves(plan.args)
            flat_specs = jax.tree.leaves(
                plan.in_specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))
            assert flat_args and flat_specs, "no args or specs"
            result = "ok"
        except Exception as e:
            result = f"{type(e).__name__}: {e}".replace("\\n", " ")
        print(f"plan\\t{name}\\t{shape}\\t{result}", flush=True)
"""


@pytest.fixture(scope="module")
def built_plans():
    """{(arch, cell): "ok" or the error}, from one subprocess."""
    out = subprocess.run(
        [sys.executable, "-c", PLAN_SCRIPT], env=ENV, capture_output=True,
        text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [ln.split("\t", 3) for ln in out.stdout.splitlines()
             if ln.startswith("plan\t")]
    return {(arch, cell): result for _, arch, cell, result in lines}


class TestPlanConstruction:
    @pytest.mark.parametrize("arch,cell", PLAN_CELLS)
    def test_all_cells_build_plans_on_production_mesh(self, built_plans,
                                                      arch, cell):
        """Each (arch x cell) builds its CellPlan (specs + ShapeDtypeStruct
        args) under the 512-device mesh without errors — the cheap
        structural check in front of the full dry-run."""
        result = built_plans.get((arch, cell), "not built")
        assert result == "ok", result
