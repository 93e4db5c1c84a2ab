"""What a configuration's file fixes: per-table sizes, pooling, table dtype
and reference, read the same way by the traffic, the reference, the work
counts and the harness.

``GOLDEN`` holds what the benchmark computed for ``dlrm_rm2`` before the
file could state per-table sizes (its pool, schedule, work counts and
reference logits at one seed); they must not move by one bit. ``TINY`` is
a synthetic file with a vocabulary and a pooling of its own for each table.
"""

import dataclasses
import hashlib
import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest
from chipbench import config, harness, program, scopes, yardstick
from chipbench import traffic as tr

HERE = Path(__file__).resolve().parent
RM2 = json.loads((HERE / "configs" / "dlrm_rm2.json").read_text())
TRAFFIC = tr.Traffic.load(HERE / "traffic" / "rm2.zipf_hot_poisson.json")
PEAKS = yardstick.load_peaks(HERE / "peaks.json", "TPU v5 lite")
SEED = 2 ** 31 + 4099

GOLDEN = {
    "pool_indices_sha256":
        "e7a41397cd2922d3234ec81e3b84dd21ed2d76c0a5fa59e1955f886409c96a3d",
    "pool_dense_sha256":
        "b2f1b78e2a567593a79af908b2a9a35f458fbdb2ad4fb78332213c486bddc11b",
    "schedule_sha256": [
        "c27461e292878de339ceea3d2bbd1927eda0e361781acb5bf22f06fefbe1d18e",
        "1ecd940dbbba6393ccbc1cda8b732c07f868ba700d14913ce20958aac1c04dd6"],
    "flops_per_sample": 1698176,
    "step_bytes": 8409860,
    "sls_least_time_s": 5.89143833943834e-06,
    "logits_highest_bits": [
        "3d9143ec", "3e4a4a47", "3ea6b0d0", "3e5e95dc", "3e5a0b7a",
        "3e01539e", "3e9163be", "3e9ba062", "3e5c9070", "3e674d0f",
        "3e674e06", "3e884d92", "3e71f4c6", "3e6c69f0", "3e81805a",
        "3e694514"],
    "logits_high3_bits": [
        "3d9143ec", "3e4a4a4c", "3ea6b16d", "3e5e95f8", "3e5a0ba8",
        "3e0153c2", "3e916365", "3e9ba083", "3e5c90d5", "3e674d5d",
        "3e674e5c", "3e884dff", "3e71f4a7", "3e6c6a1b", "3e818036",
        "3e694537"],
}

TINY = {"name": "tiny", "arch": "dlrm_rm2", "reference": "dlrm",
        "n_dense": 3, "n_tables": 4, "n_rows": [5000, 3, 1000, 70],
        "embed_dim": 8, "lookups": [3, 1, 7, 2], "bot_mlp": [16, 8],
        "top_mlp": [16], "interaction": "dot", "dtype": "float32",
        "matmul_precision": "highest", "max_batch": 8, "max_wait_us": 1000.0,
        "sample_inferences": 16, "k": 0.0, "logit_gap_limit": 1e-05}


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def _bits(x: np.ndarray) -> list[str]:
    return [f"{b:08x}" for b in np.asarray(x, np.float32).view(np.uint32)]


def _pool(cfg: dict, pool: int, seed: int = SEED):
    return tr.make_pool(dataclasses.replace(TRAFFIC, pool=pool),
                        config.per_table(cfg, "n_rows"),
                        config.per_table(cfg, "lookups"), cfg["n_dense"],
                        seed)


# ------------------------------------------------ dlrm_rm2 does not move
@pytest.mark.parametrize("what", ["pool", "schedule", "work", "logits"])
def test_dlrm_rm2_reads_what_it_read_before(what):
    reference = config.reference(RM2)
    if what == "pool":
        idx, dense = _pool(RM2, 512)
        assert idx.shape == (512, 26, 80) and idx.dtype == np.int32
        assert _sha(idx) == GOLDEN["pool_indices_sha256"]
        assert _sha(dense) == GOLDEN["pool_dense_sha256"]
    elif what == "schedule":
        assert [_sha(a) for a in tr.make_schedule(TRAFFIC, 2.0, SEED)] \
            == GOLDEN["schedule_sha256"]
    elif what == "work":
        batch = _pool(RM2, 512)[0][:64]
        assert reference.flops_per_sample(RM2) == GOLDEN["flops_per_sample"]
        assert reference.step_bytes(RM2, batch) == GOLDEN["step_bytes"]
        assert scopes.sls_least_time_s(RM2, batch, PEAKS) \
            == GOLDEN["sls_least_time_s"]
    else:
        small = dict(RM2, n_rows=5000)
        idx, dense = _pool(small, 16)
        for precision in ("highest", "high3"):
            got = reference.logits(small, harness.weight_seed(SEED), idx,
                                   dense, precision)
            assert _bits(got) == GOLDEN[f"logits_{precision}_bits"]


# ------------------------------------------------------ per-table forms
def test_one_number_and_a_list_of_equal_numbers_are_one_form():
    listed = dict(RM2, n_rows=[1000] * 26, lookups=[80] * 26)
    single = dict(RM2, n_rows=1000)
    for key in ("n_rows", "lookups"):
        assert config.per_table(listed, key) == config.per_table(single, key)
    a, b = _pool(listed, 8), _pool(single, 8)
    assert a[0].shape == (8, 26, 80)
    for x, y in zip(a, b, strict=True):
        np.testing.assert_array_equal(x, y)
    for bad in (7.5, [80] * 25, [80] * 25 + [0], None):
        with pytest.raises(harness.BenchError, match="lookups"):
            config.per_table(dict(RM2, lookups=bad), "lookups")


def _program_weights(cfg: dict, seed: int):
    """The tables and MLPs the program's ``dlrm.init`` draws for ``cfg``:
    tables in the file's dtype, MLPs in float32."""
    import repro.models.dlrm as dlrm

    model = dlrm.DLRMConfig(
        name=cfg["name"], n_tables=cfg["n_tables"], n_dense=cfg["n_dense"],
        embed_dim=cfg["embed_dim"], n_rows=tuple(cfg["n_rows"]), lookups=1,
        bot_mlp=tuple(cfg["bot_mlp"]), top_mlp=tuple(cfg["top_mlp"]))
    key = jax.random.PRNGKey(seed)
    return (dlrm.init(key, model, config.table_dtype(cfg))["tables"],
            dlrm.init(key, model))


def _numpy_logits(cfg: dict, tables: list, mlps: dict, idx: np.ndarray,
                  dense: np.ndarray) -> np.ndarray:
    """SLS, bottom MLP, dot interaction and top MLP in float64 numpy."""
    def mlp(layers, x):
        for i, layer in enumerate(layers):
            x = x @ np.asarray(layer["w"], np.float64) + np.asarray(
                layer["b"], np.float64)
            x = np.maximum(x, 0) if i < len(layers) - 1 else x
        return x

    cols = config.columns(config.per_table(cfg, "lookups"))
    x = mlp(mlps["bot"], dense.astype(np.float64))
    bags = [np.asarray(table, np.float64)[idx[:, c]].sum(axis=1)
            for table, c in zip(tables, cols, strict=True)]
    z = np.stack([x] + bags, axis=1)
    dots = np.einsum("bid,bjd->bij", z, z)
    iu, ju = np.triu_indices(z.shape[1], k=1)
    return mlp(mlps["top"], np.concatenate([x, dots[:, iu, ju]], 1))[:, 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tables_of_their_own_sizes(dtype):
    cfg = dict(TINY, dtype=dtype)
    n_rows, lookups = cfg["n_rows"], cfg["lookups"]
    idx, dense = _pool(cfg, 64)

    # every id in its own table's columns and range, drawn from its own
    # table's Zipf CDF and popularity permutation
    assert idx.shape == (64, 13) and idx.dtype == np.int32
    u = np.random.default_rng([SEED, 0]).random((64, 13))
    offsets = np.cumsum([0] + lookups)
    for t, (n, lo, hi) in enumerate(zip(n_rows, offsets[:-1], offsets[1:],
                                        strict=True)):
        block = idx[:, lo:hi]
        assert 0 <= block.min() and block.max() < n
        cdf = np.cumsum(tr.zipf_probs(n, TRAFFIC.alpha))
        ranks = np.minimum(np.searchsorted(cdf, u[:, lo:hi], side="right"),
                           n - 1)
        np.testing.assert_array_equal(
            block, tr.popularity_perm(n, t, TRAFFIC.pop_seed)[ranks])
    assert len(np.unique(idx[:, 3])) == 3         # table 1: 3 rows, 1 lookup

    # the reference against plain numpy on the program's own draws
    reference = config.reference(cfg)
    tables, mlps = _program_weights(cfg, 11)
    keys = jax.random.split(jax.random.PRNGKey(11), cfg["n_tables"] + 2)
    for t, table in enumerate(tables):
        assert table.dtype == config.table_dtype(cfg)
        np.testing.assert_array_equal(
            reference._table(keys[t], n_rows[t], 8, dtype), table)
    got = reference.logits(cfg, 11, idx, dense, block=24)
    want = _numpy_logits(cfg, tables, mlps, idx, dense)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.sqrt(np.mean(want ** 2)))

    # the work counts, by hand: bottom 3-16-8, top 18-16-1 (18 = 8 + 10
    # pairs of 5 vectors), 10 pairs x 8, one add per pooled element
    assert reference.flops_per_sample(cfg) \
        == 2 * (3 * 16 + 16 * 8) + 2 * (18 * 16 + 16 * 1) + 10 * 2 * 8 \
        + 13 * 8
    two = np.array([[5, 5, 7, 1, 0, 1, 2, 3, 4, 5, 6, 9, 9],
                    [7, 8, 5, 1, 0, 0, 0, 0, 0, 0, 0, 9, 10]])
    distinct = 3 + 1 + 7 + 2
    assert yardstick.distinct_rows(two, tuple(lookups)) == distinct
    row_bytes = distinct * 8 * {"float32": 4, "bfloat16": 2}[dtype]
    weights = 4 * (3 * 16 + 16 + 16 * 8 + 8 + 18 * 16 + 16 + 16 + 1)
    assert reference.mlp_weight_bytes(cfg) == weights
    assert reference.step_bytes(cfg, two) \
        == row_bytes + weights + 2 * 3 * 4 + 2 * 13 * 4 + 2 * 4
    assert reference.sls_work(cfg, two) == (2 * 13 * 8, row_bytes)


# ------------------------------------------ what the program does not run
def _cell(cfg: dict) -> harness.Cell:
    cell = harness.load_cell("rm2.hot.rate")
    return dataclasses.replace(
        cell, cfg=cfg, traffic=dataclasses.replace(cell.traffic, pool=64))


@pytest.mark.parametrize("key, cfg", [
    ("lookups", TINY),
    ("dtype", dict(RM2, n_rows=5000, dtype="bfloat16")),
    ("embed_dim", dict(RM2, n_rows=5000, embed_dim=32)),
    ("interaction", dict(RM2, n_rows=5000, interaction="concat")),
], ids=["pooling_list", "bfloat16", "embed_dim", "interaction"])
def test_a_file_the_program_does_not_run_is_refused(key, cfg, monkeypatch):
    """Refused before the window, naming the key; a per-table form the
    program cannot take never reaches it."""
    from repro.serving import DeploymentConfig

    if key == "lookups":
        monkeypatch.setattr(DeploymentConfig, "from_arch", classmethod(
            lambda *a, **k: pytest.fail("the program was called")))
    monkeypatch.setattr(harness, "_sleep_until", lambda _t: pytest.fail(
        "the window was opened"))
    with pytest.raises(harness.BenchError, match=key):
        harness.run_cell(_cell(cfg), SEED, 0.5, False, require_tpu=False,
                         log=lambda *a, **k: None)


def test_an_unknown_reference_names_its_path():
    cfg = dict(RM2, reference="no_such_model")
    path = HERE / "references" / "no_such_model.py"
    with pytest.raises(harness.BenchError, match=re.escape(str(path))):
        harness.run_cell(_cell(cfg), SEED, 0.5, False, require_tpu=False)
    with pytest.raises(harness.BenchError, match="references"):
        config.reference(dict(RM2, reference="../chipbench/harness"))


def test_a_per_table_form_goes_where_the_program_takes_one():
    assert not program._takes_sequence(int | None)
    assert program._takes_sequence(int | tuple[int, ...] | None)
    assert program._takes_sequence(list[int] | None)
