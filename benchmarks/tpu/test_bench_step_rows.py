"""``step_rows.rate``: the row bucket each traced step ran, read from the
``rows_<k>`` scope of its ops, on synthetic traces of a compiled step whose
translation runs in one branch per bucket."""

import json
from pathlib import Path

import numpy as np
import pytest
from chipbench import harness, scopes

HERE = Path(__file__).resolve().parent
CFG = json.loads((HERE / "configs" / "dlrm_rm2.json").read_text())
BRANCH = ('  %gather.{k} = s32[{k},4]{{1,0}} gather(s32[{k},4]{{1,0}} %p.{k}), '
          'metadata={{op_name="jit(serve_step)/translate/cond/branch_{i}_fun/'
          'rows_{k}/translate/jit(_take)/gather"}}')
ENTRY = """
ENTRY %main.9 (idx.1: s32[64,4]) -> f32[64] {
  %fusion.1 = s32[] fusion(s32[64,4]{1,0} %idx.1), metadata={op_name="jit(serve_step)/translate/reduce_max"}
  %cond.2 = (s32[64,4]{1,0}) conditional(s32[] %fusion.1), metadata={op_name="jit(serve_step)/translate/cond"}
  ROOT %fusion.3 = f32[64]{0} fusion(%cond.2), metadata={op_name="jit(serve_step)/mlp/dot_general"}
}
"""


def _hlo(buckets=(8, 16, 32, 64), scoped=True) -> str:
    """The compiled step: one branch computation per bucket; without
    ``scoped`` every op name is a path of no scope."""
    text = "HloModule jit_serve_step, is_scheduled=true\n" + "".join(
        f"\n%region_{i} (p.{k}: s32[{k},4]) -> s32[64,4] {{\n"
        + BRANCH.format(i=i, k=k) + "\n}\n" for i, k in enumerate(buckets)
    ) + ENTRY
    if not scoped:
        for scope in ("translate", "mlp") + tuple(
                f"rows_{k}" for k in buckets):
            text = text.replace(f"/{scope}/", "/")
    return text


def _run(branches: list, monkeypatch, hlo: str) -> harness.Run:
    """A run of one dispatch of four rows per step; step ``j`` runs the
    branch of ``branches[j]`` rows inside the ``conditional``, or none."""
    monkeypatch.setattr(scopes, "step_hlo", lambda cfg, shape: hlo)
    ops, modules, t = [], [], 100
    for k in branches:
        ops += [["%fusion.1 = s32[] fusion()", t + 1, 10],
                ["%cond.2 = (s32[64,4]) conditional()", t + 12, 12],
                ["%fusion.3 = f32[64]{0} fusion()", t + 25, 10]]
        if k is not None:
            ops.append([f"%gather.{k} = s32[{k},4] gather()", t + 13, 10])
        modules.append(["jit_serve_step(1)", t, 36])
        t += 100
    tr = {"ops": sorted(ops, key=lambda ev: ev[1]), "modules": modules,
          "spans": [["window", 0, t + 10]]}
    n = len(branches)
    pool = np.zeros((4 * n, CFG["n_tables"], CFG["lookups"]), np.int64)
    return harness.Run(
        cfg=CFG, max_batch=CFG["max_batch"], pool_indices=pool,
        batches=[np.arange(4 * b, 4 * b + 4) for b in range(n)],
        waiting=np.zeros(n, bool), latency_ms=np.ones(4 * n), trace=tr)


READ = harness.load_reader("step_rows.rate")


@pytest.mark.parametrize("branches,rows", [
    ([8, 16, 8, 64], 24.0),
    ([8] * 7, 8.0),
    ([32, 64], 48.0),
    # a step in which no op of a bucket ran computes every row
    ([8, None], 36.0)])
def test_step_rows_is_the_mean_bucket_of_the_steps(monkeypatch, branches,
                                                   rows):
    assert READ(_run(branches, monkeypatch, _hlo())) == rows


def test_a_step_without_buckets_computes_its_whole_batch(monkeypatch):
    """The scoped step before it was bucketed: no op of a ``rows_<k>``
    scope, so every step computes ``max_batch`` rows."""
    hlo = _hlo().replace("/rows_", "/all_")
    assert READ(_run([8, 16], monkeypatch, hlo)) == CFG["max_batch"]


def test_a_step_without_scopes_reads_null(monkeypatch):
    assert READ(_run([8, 16], monkeypatch, _hlo(scoped=False))) is None
