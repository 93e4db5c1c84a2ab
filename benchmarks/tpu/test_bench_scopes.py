"""The split of each step's device time by the program's named scopes.

``testdata/trace_rm2_scoped.json`` is a cut of a chip trace: a traced
``rm2.hot.rate`` run on one TPU v5 lite (seed 2147491234, a 10-s window),
three consecutive dispatches with every device op, step execution and host
span, the window span cut to them. Op names are as the trace gives them
(the instruction's HLO text); ``op_scopes`` is ``scopes.op_scopes`` of the
run's compiled step for those ops; each dispatch keeps its real rows and
the distinct rows of each table, which is all the yardstick reads.

The expected numbers are worked out here by brute force over elementary
time segments, independently of the readers' interval arithmetic. The
compiles run on the CPU with the tables cut to 5,000 rows; every width is
the configuration's.
"""

import dataclasses
import functools
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench import config, harness, scopes, trace, yardstick

HERE = Path(__file__).resolve().parent
TRACE = json.loads((HERE / "testdata" / "trace_rm2_small.json").read_text()
                   )["trace"]
SCOPED = json.loads((HERE / "testdata" / "trace_rm2_scoped.json").read_text())
CFG = json.loads((HERE / "configs" / "dlrm_rm2.json").read_text())
SMALL = dict(CFG, n_rows=5000)
SHAPE = config.index_shape(config.per_table(CFG, "lookups"))
PEAKS = yardstick.load_peaks(HERE / "peaks.json", "TPU v5 lite")
EXISTING = ("p99_ms.rate", "batch_fill.rate", "host_gap_ms.rate",
            "step_device_ms.rate", "step_mfu.rate", "idle_share.rate")
SCOPE_READERS = {"translate_device_ms.rate": "translate",
                 "sls_device_ms.rate": "sls",
                 "interact_device_ms.rate": "interact",
                 "mlp_device_ms.rate": "mlp"}
NEW = list(SCOPE_READERS) + ["sls_roofline.rate", "unscoped_device_ms.rate"]
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@functools.cache
def _small_step_hlo() -> str:
    return scopes.step_hlo(SMALL, SHAPE)


def _entry(hlo_text):
    """``(name, opcode)`` of each instruction of the entry computation."""
    body = hlo_text[hlo_text.index("\nENTRY"):].split("\n}", 1)[0]
    return re.findall(r"^\s+(?:ROOT )?%([^\s=]+) = .*? ([a-z][a-z0-9-]*)\(",
                      body, re.M)


# ------------------------------------------------ the map from the HLO text
def test_every_op_of_the_served_step_resolves_to_one_scope():
    text = _small_step_hlo()
    found = scopes.op_scopes(text)
    work = [name for name, opcode in _entry(text) if opcode not in NO_WORK]
    assert len(work) > 10
    for name in work:
        assert sum(scopes.in_scope(found[name], s)
                   for s in scopes.SCOPES) == 1, (name, found[name])
    # the CPU compiler may fuse the translation's gather with the SLS's, so
    # each scope is looked for in every computation
    seen = {s for op_name in found.values() for s in scopes.SCOPES
            if scopes.in_scope(op_name, s)}
    assert seen == set(scopes.SCOPES)


HLO_SNIPPET = """HloModule jit_serve_step, is_scheduled=true

%fused_computation (param_0: f32[8,4]) -> f32[8] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%param_0), dimensions={1}, metadata={op_name="jit(serve_step)/sls/reduce_sum"}
}

ENTRY %main.9 (table.1: f32[100,4], idx.1: s32[8]) -> f32[8] {
  %table.1 = f32[100,4]{0,1} parameter(0), metadata={op_name="params[\\'tables\\'][0]"}
  %idx.1 = s32[8]{0} parameter(1), metadata={op_name="batch[\\'indices\\']"}
  %copy.55 = f32[100,4]{1,0} copy(%table.1), metadata={op_name="params[\\'tables\\'][0]"}
  %copy-start = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%idx.1)
  %copy-done = s32[8]{0} copy-done(%copy-start)
  %clamp_fusion = s32[8]{0} fusion(%copy-done), kind=kLoop, calls=%fc.2, metadata={op_name="gather"}
  %gather.3 = f32[8,4]{1,0} gather(f32[100,4]{1,0} %copy.55, s32[8]{0} %clamp_fusion), offset_dims={1}, metadata={op_name="jit(serve_step)/sls/jit(_take)/gather" stack_frame_id=3}
  %fusion.4 = f32[8]{0} fusion(%gather.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(serve_step)/sls/reduce_sum"}
  %copy.6 = f32[8]{0} copy(%fusion.4)
  %orphan = f32[8]{0} copy(%fusion.4), metadata={op_name="reduce_window_sum"}
  ROOT %add.7 = f32[8]{0} add(%copy.6, %fusion.4), metadata={op_name="jit(serve_step)/mlp/add"}
}
"""


def test_op_scopes_follow_the_first_consumer_with_a_path():
    found = scopes.op_scopes(HLO_SNIPPET)
    sls = "jit(serve_step)/sls/jit(_take)/gather"
    # a parameter's name copied onto the relayout copy is not a path: the
    # copy counts in the scope of its consumer, the gather
    assert found["copy.55"] == sls
    assert found["table.1"] == sls
    # unnamed and pass-named instructions through a chain of consumers
    assert found["copy-start"] == found["copy-done"] == sls
    assert found["clamp_fusion"] == sls
    assert found["copy.6"] == "jit(serve_step)/mlp/add"
    assert found["orphan"] == ""
    # instructions of called computations are mapped too
    assert found["reduce.1"] == "jit(serve_step)/sls/reduce_sum"
    assert "main.9" not in found and "fused_computation" not in found


def test_instruction_is_the_name_in_the_event():
    name = ("%copy.55 = f32[1000000,64]{1,0:T(8,128)} copy(f32[1000000,64]"
            "{0,1:T(8,128)} %params__tables___1_.1)")
    assert scopes.instruction(name) == "copy.55"
    assert scopes.instruction("copy.55") == "copy.55"
    assert scopes.instruction("%fusion.3=f32[8]") == "fusion.3"
    assert all(scopes.instruction(n) in SCOPED["op_scopes"]
               for n, _, _ in SCOPED["trace"]["ops"])


# ------------------------------------------------- the step compiled again
def test_the_step_compiled_again_is_the_step_the_harness_compiles():
    """The readers' compile gives the text, metadata and instruction names
    included, of the harness's compile on tables of other values."""
    from repro.embedding.layout import RemapSpec
    from repro.launch import serve
    from repro.serving import DeploymentConfig, arch_model_config

    model = arch_model_config(DeploymentConfig.from_arch(
        SMALL["arch"], n_rows=SMALL["n_rows"], policies=()))
    rng = np.random.default_rng(3)
    specs = [RemapSpec.from_counts(rng.integers(0, 50, n))
             for n in model.n_rows]
    params, rank_ofs = serve.place_tables(model, specs, 11)
    shape = {"dense": jax.ShapeDtypeStruct((SMALL["max_batch"],
                                            model.n_dense), jnp.float32),
             "indices": jax.ShapeDtypeStruct(
                 (SMALL["max_batch"], model.n_tables, model.lookups),
                 jnp.int32)}
    with jax.default_matmul_precision(SMALL["matmul_precision"]):
        text = serve.serve_step.lower(params, rank_ofs, shape,
                                      cfg=model).compile().as_text()
    assert _small_step_hlo() == text


@pytest.mark.parametrize("was", [False, True])
def test_the_compile_keys_the_cache_on_metadata(monkeypatch, was):
    """An executable loaded from the persistent cache keeps the op names of
    the program that compiled it first unless the key holds the metadata,
    and the in-memory caches give back the harness's executable unless
    they are cleared first."""
    from repro.launch import serve

    key = "jax_compilation_cache_include_metadata_in_key"
    seen = []
    real = serve.serve_step
    clear = jax.clear_caches
    monkeypatch.setattr(jax, "clear_caches",
                        lambda: (seen.append("cleared"), clear()))

    class Spy:
        def lower(self, *args, **kwargs):
            seen.append(getattr(jax.config, key))
            return real.lower(*args, **kwargs)

    monkeypatch.setattr(serve, "serve_step", Spy())
    jax.config.update(key, was)
    try:
        assert "jit_serve_step" in scopes.step_hlo(SMALL, SHAPE)
        assert seen == ["cleared", True]
        assert getattr(jax.config, key) is was
    finally:
        jax.config.update(key, False)


SHARED_CACHE = """
import contextlib, json, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from chipbench import scopes
from repro.embedding.layout import RemapSpec
from repro.launch import serve
from repro.serving import DeploymentConfig, arch_model_config
cfg = json.loads(sys.argv[2])
model = arch_model_config(DeploymentConfig.from_arch(
    cfg["arch"], n_rows=cfg["n_rows"], policies=()))
specs = [RemapSpec.from_counts(np.arange(n) % 7) for n in model.n_rows]
params, rank_ofs = serve.place_tables(model, specs, 3)
shape = {"dense": jax.ShapeDtypeStruct((cfg["max_batch"], model.n_dense),
                                       jnp.float32),
         "indices": jax.ShapeDtypeStruct(
             (cfg["max_batch"], model.n_tables, model.lookups), jnp.int32)}
if sys.argv[3] == "without":
    jax.named_scope = lambda _: contextlib.nullcontext()
with jax.default_matmul_precision(cfg["matmul_precision"]):
    step = serve.serve_step.lower(params, rank_ofs, shape, cfg=model).compile()
print(json.dumps({"step": "/sls/" in step.as_text(),
                  "readers": "/sls/" in scopes.step_hlo(
                      cfg, (cfg["n_tables"], cfg["lookups"]))}))
"""


def test_the_readers_get_their_own_op_names_from_a_shared_cache(tmp_path):
    """A program with the same ops but no scopes fills the persistent cache
    first, as another side of a comparison may when both share one; the
    harness's step then carries no scope, and the readers' compile still
    reads the program's own. Each side is a process of its own."""
    import os
    import subprocess
    import sys

    def side(scoped: str) -> dict:
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [str(HERE), str(HERE.parents[1] / "src")]))
        out = subprocess.run(
            [sys.executable, "-c", SHARED_CACHE, str(tmp_path / "cache"),
             json.dumps(SMALL), scoped],
            env=env, capture_output=True, text=True, check=True).stdout
        return json.loads(out.strip().splitlines()[-1])

    assert side("without") == {"step": False, "readers": False}
    assert side("with") == {"step": False, "readers": True}


def _laid_out(names: list[str], batches: int = 2):
    """A trace with ``batches`` steps, each running ``names`` one after the
    other with a gap of 1 ns between ops, and the run that recorded it."""
    ops, modules, t = [], [], 100
    for _ in range(batches):
        start = t
        for name in names:
            ops.append([f"%{name} = f32[8]{{0}} op()", t + 1, 10])
            t += 11
        modules.append(["jit_serve_step(1)", start, t + 1 - start])
        t += 50
    tr = {"ops": ops, "modules": modules, "spans": [["window", 0, t + 10]]}
    rng = np.random.default_rng(5)
    pool = rng.integers(0, SMALL["n_rows"], size=(
        4 * batches, SMALL["n_tables"], SMALL["lookups"]))
    run = harness.Run(
        cfg=SMALL, max_batch=SMALL["max_batch"], pool_indices=pool,
        batches=[np.arange(4 * b, 4 * b + 4) for b in range(batches)],
        waiting=np.zeros(batches, bool), latency_ms=np.ones(4 * batches),
        peaks=PEAKS, trace=tr)
    return tr, run


def test_the_readers_compile_the_step_and_split_it():
    text = _small_step_hlo()
    work = [name for name, opcode in _entry(text) if opcode not in NO_WORK]
    _, run = _laid_out(work)
    found = scopes.op_scopes(text)
    per_op = 10e-6
    for metric, scope in SCOPE_READERS.items():
        mine = sum(scopes.in_scope(found[n], scope) for n in work)
        assert harness.load_reader(metric)(run) == pytest.approx(
            mine * per_op)
    assert run.op_scopes == found
    # one idle ns before each op and after the last, inside the step
    assert harness.load_reader("unscoped_device_ms.rate")(run) \
        == pytest.approx((len(work) + 1) * 1e-6)
    assert 0 < harness.load_reader("sls_roofline.rate")(run)


def test_an_op_the_compiled_step_lacks_makes_every_reading_null():
    work = [name for name, opcode in _entry(_small_step_hlo())
            if opcode not in NO_WORK]
    _, run = _laid_out(work + ["no_such_instruction.1"])
    assert all(harness.load_reader(m)(run) is None for m in NEW)
    assert run.op_scopes is None


def test_a_trace_without_steps_reads_null_and_compiles_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "step_hlo", lambda cfg, shape: pytest.fail(
        "compiled without steps to read"))
    _, run = _laid_out(["fusion.1"])
    run.trace = dict(run.trace, modules=[])
    assert all(harness.load_reader(m)(run) is None for m in NEW)


NESTED_HLO = """HloModule jit_serve_step, is_scheduled=true

ENTRY %main.5 (idx.1: s32[8]) -> f32[8] {
  %gather.1 = f32[8]{0} gather(s32[8]{0} %idx.1), metadata={op_name="jit(serve_step)/sls/jit(_take)/gather"}
  %dot.2 = f32[8]{0} dot(f32[8]{0} %gather.1), metadata={op_name="jit(serve_step)/interact/cross/dot_general"}
  %add.3 = f32[8]{0} add(f32[8]{0} %dot.2), metadata={op_name="jit(serve_step)/interact/add"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %add.3)
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %add.3), metadata={op_name="jit(serve_step)/mlp/dot_general"}
}
"""


def test_a_scope_nested_in_another_counts_in_both(monkeypatch):
    """An op of ``interact/cross`` is read as ``cross`` and counts in
    ``interact``, not in the time of no scope; a scope that no op has reads
    null, not 0."""
    monkeypatch.setattr(scopes, "step_hlo", lambda cfg, shape: NESTED_HLO)
    _, run = _laid_out(["gather.1", "dot.2", "add.3", "copy.4", "fusion.5"])
    per_op = 10e-6
    assert scopes.scope_ms(run, "cross") == pytest.approx(per_op)
    assert scopes.scope_ms(run, "interact") == pytest.approx(2 * per_op)
    assert scopes.scope_ms(run, "sls") == pytest.approx(per_op)
    assert scopes.scope_ms(run, "mlp") == pytest.approx(per_op)
    # an idle ns before each op and after the last, and the orphan copy
    assert scopes.unscoped_ms(run) == pytest.approx(6e-6 + per_op)
    assert harness.load_reader("interact_device_ms.rate")(run) \
        == pytest.approx(2 * per_op)
    for absent in ("translate", "no_such_scope"):
        assert scopes.scope_ms(run, absent) is None
    assert harness.load_reader("translate_device_ms.rate")(run) is None


def test_a_trace_cut_short_reads_the_dispatches_it_holds():
    """The profiler keeps a fixed number of device events and drops the
    rest, so a long window holds the steps of its first dispatches only.
    The step readers then read those dispatches as a run of only them
    reads, and the traced window ends with the last step whose every op
    was kept; a trace that holds every step reads as before."""
    text = _small_step_hlo()
    work = [name for name, opcode in _entry(text) if opcode not in NO_WORK]
    _, run = _laid_out(work, batches=4)
    run = dataclasses.replace(run, waiting=np.ones(4, bool))
    tr, n = run.trace, len(work)
    # the third step's module was kept, its ops only in part; the fourth
    # dispatch left nothing
    cut = dataclasses.replace(run, trace=dict(
        tr, modules=tr["modules"][:3], ops=tr["ops"][:2 * n + 1]))
    head = dataclasses.replace(
        run, batches=run.batches[:2], waiting=run.waiting[:2],
        trace=dict(tr, modules=tr["modules"][:2], ops=tr["ops"][:2 * n]))
    for r in (cut, head, run):
        r.op_scopes = scopes.op_scopes(text)
    lo = trace.window(tr)[0]
    name, start, length = tr["modules"][1]
    assert cut.steps() == head.steps() == [
        (s, s + d) for _, s, d in tr["modules"][:2]]
    assert cut.window() == (lo, start + length)
    assert head.window() == run.window() == trace.window(tr)
    assert len(run.steps()) == 4
    for metric in ["step_device_ms.rate", "host_gap_ms.rate",
                   "step_mfu.rate", *NEW]:
        value = harness.load_reader(metric)(cut)
        assert value is not None and value == pytest.approx(
            harness.load_reader(metric)(head)), metric
    busy = trace.Busy.of(trace.Ops.of(cut.trace["ops"]),
                         *cut.window()).busy_ns
    assert harness.load_reader("idle_share.rate")(cut) == pytest.approx(
        100.0 * (1.0 - busy / (start + length - lo)))
    # more step executions than dispatches: no steps, as before
    extra = dataclasses.replace(run, batches=run.batches[:3])
    assert extra.steps() is None and extra.window() == trace.window(tr)


# ------------------------------------------------ the cut of a chip trace
def _scoped_run(op_scopes):
    """A ``Run`` of the scoped cut; each dispatch's rows rebuilt with the
    recorded number of distinct rows per table."""
    rows, pools = [], []
    for b in SCOPED["batches"]:
        idx = np.empty((b["rows"], CFG["n_tables"], CFG["lookups"]),
                       np.int64)
        for t, d in enumerate(b["distinct"]):
            idx[:, t] = (np.arange(idx[:, t].size) % d).reshape(
                idx.shape[0], -1)
        rows.append(b["rows"])
        pools.append(idx)
    ends = np.cumsum(rows)
    run = harness.Run(
        cfg=CFG, max_batch=CFG["max_batch"],
        pool_indices=np.concatenate(pools),
        batches=[np.arange(e - n, e) for n, e in zip(rows, ends,
                                                     strict=True)],
        waiting=np.array(SCOPED["waiting"]),
        latency_ms=np.linspace(40.0, 80.0, int(ends[-1])), peaks=PEAKS,
        trace=SCOPED["trace"])
    if op_scopes is not ...:
        run.op_scopes = op_scopes
    return run


def _brute_split():
    """Per step: ns covered by ops of each scope, and by no scoped op."""
    tr, found = SCOPED["trace"], SCOPED["op_scopes"]
    lo, hi = trace.window(tr)
    out = []
    for s0, e0 in trace.steps(tr, lo, hi):
        split = dict.fromkeys(scopes.SCOPES + ("",), 0.0)
        cuts = sorted({s0, e0} | {t for _, s, d in tr["ops"]
                                  for t in (s, s + d) if s0 < t < e0})
        for a, b in zip(cuts[:-1], cuts[1:], strict=True):
            names = {found.get(scopes.instruction(n), "")
                     for n, s, d in tr["ops"] if s <= a and b <= s + d}
            covering = {sc for sc in scopes.SCOPES
                        if any(sc in name.split("/") for name in names)}
            assert len(covering) <= 1
            split[covering.pop() if covering else ""] += b - a
        out.append((e0 - s0, split))
    return out


def test_scopes_and_unscoped_time_make_up_each_step():
    split = _brute_split()
    assert len(split) == SCOPED["dispatches"]
    for step_ns, parts in split:
        assert sum(parts.values()) == pytest.approx(step_ns, abs=1e-3)
        assert parts["sls"] > 0.9 * step_ns and parts[""] < 0.03 * step_ns
    run = _scoped_run(SCOPED["op_scopes"])
    steps = run.steps()
    ops = trace.Ops.of(SCOPED["trace"]["ops"])
    found = scopes.split(ops.names, ops.clip(steps), steps,
                         SCOPED["op_scopes"])
    for scope in scopes.SCOPES:
        assert found.segment_ns[scope] == pytest.approx(
            [parts[scope] for _, parts in split], abs=1e-3)
    assert found.unscoped_ns == pytest.approx(
        [parts[""] for _, parts in split], abs=1e-3)
    step_ms = harness.load_reader("step_device_ms.rate")(run)
    total = sum(harness.load_reader(m)(run) for m in SCOPE_READERS)
    assert total + harness.load_reader("unscoped_device_ms.rate")(run) \
        == pytest.approx(step_ms, rel=1e-9)


def test_the_new_readers_on_the_scoped_cut():
    run = _scoped_run(SCOPED["op_scopes"])
    split = _brute_split()
    for metric, scope in SCOPE_READERS.items():
        want = np.mean([parts[scope] for _, parts in split]) * 1e-6
        assert harness.load_reader(metric)(run) == pytest.approx(want)
    assert harness.load_reader("unscoped_device_ms.rate")(run) \
        == pytest.approx(np.mean([parts[""] for _, parts in split]) * 1e-6)
    # the 26 relayout copies of the tables count in sls
    copies = [n for n, _, _ in SCOPED["trace"]["ops"]
              if re.match(r"%copy\.\d+ = f32\[1000000,64\]", n)]
    assert len(copies) == 26 * SCOPED["dispatches"]
    assert all(scopes.in_scope(SCOPED["op_scopes"][scopes.instruction(n)],
                               "sls") for n in copies)
    least = 0.0
    for b in SCOPED["batches"]:
        flops = b["rows"] * CFG["n_tables"] * CFG["lookups"] * CFG[
            "embed_dim"]
        n_bytes = sum(b["distinct"]) * CFG["embed_dim"] * 4
        least += max(flops / PEAKS["flops_per_s"],
                     n_bytes / PEAKS["hbm_bytes_per_s"])
    sls_s = sum(parts["sls"] for _, parts in split) * 1e-9
    roofline = harness.load_reader("sls_roofline.rate")(run)
    assert roofline == pytest.approx(100.0 * least / sls_s)
    assert 0 < roofline <= 100


def test_a_scope_no_op_resolves_to_reads_null():
    run = _scoped_run(SCOPED["op_scopes"])
    assert scopes.scope_ms(run, "no_such_scope") is None
    renamed = {k: v.replace("/sls/", "/lookup/")
               for k, v in SCOPED["op_scopes"].items()}
    run = _scoped_run(renamed)
    assert scopes.scope_ms(run, "sls") is None
    assert harness.load_reader("sls_roofline.rate")(run) is None
    assert scopes.scope_ms(run, "translate") is not None
    # a program without the scopes, as before they were written
    run = _scoped_run(dict.fromkeys(SCOPED["op_scopes"], ""))
    assert all(harness.load_reader(m)(run) is None for m in NEW)
    run = _scoped_run(None)
    assert all(harness.load_reader(m)(run) is None for m in NEW)


def test_sls_least_time_counts_distinct_rows_and_pooled_adds():
    cfg = {"name": "tiny", "reference": "dlrm", "dtype": "float32",
           "n_tables": 2, "embed_dim": 4, "lookups": 3}
    indices = np.array([[[5, 5, 7], [1, 2, 3]],
                        [[7, 8, 5], [3, 3, 3]]])
    n_bytes = 6 * 4 * 4                 # 3 + 3 distinct rows x 4 x 4 B
    flops = 2 * 2 * 3 * 4               # one add per pooled element
    bytes_bound = {"flops_per_s": 1e9, "hbm_bytes_per_s": 8.0}
    assert scopes.sls_least_time_s(cfg, indices, bytes_bound) \
        == n_bytes / 8.0
    flops_bound = {"flops_per_s": 4.0, "hbm_bytes_per_s": 1e9}
    assert scopes.sls_least_time_s(cfg, indices, flops_bound) \
        == flops / 4.0


# ---------------------------------------- the readers that predate scopes
def _small_run():
    rng = np.random.default_rng(7)
    return harness.Run(
        cfg=CFG, max_batch=64, pool_indices=rng.integers(0, 500,
                                                         size=(16, 26, 80)),
        batches=[np.arange(0, 5), np.arange(5, 9), np.arange(9, 15),
                 np.arange(15, 16)],
        waiting=np.array([False, True, False, True]),
        latency_ms=np.linspace(40.0, 80.0, 33), peaks=PEAKS, trace=TRACE)


# What the readers that predate the scope metrics read on the committed
# testdata, with the harness and readers as they were before the scopes.
BEFORE_SMALL = {"p99_ms.rate": 79.6, "batch_fill.rate": 0.0625,
                "host_gap_ms.rate": 0.00465, "step_device_ms.rate": 0.019,
                "step_mfu.rate": 29.389396568343933,
                "idle_share.rate": 56.391000000000005}
BEFORE_SCOPED = {"p99_ms.rate": 79.6, "batch_fill.rate": 0.7864583333333334,
                 "host_gap_ms.rate": 2.6232905,
                 "step_device_ms.rate": 33.21287366666667,
                 "step_mfu.rate": 0.027745634417569384,
                 "idle_share.rate": 7.076212746658939}


@pytest.mark.parametrize("metric", EXISTING)
def test_existing_readers_read_what_they_read_before(metric):
    assert harness.load_reader(metric)(_small_run()) \
        == pytest.approx(BEFORE_SMALL[metric], rel=1e-12)
    assert harness.load_reader(metric)(_scoped_run(...)) \
        == pytest.approx(BEFORE_SCOPED[metric], rel=1e-12)
    assert harness.load_reader(metric)(_scoped_run(SCOPED["op_scopes"])) \
        == pytest.approx(BEFORE_SCOPED[metric], rel=1e-12)
