"""Every entry of BENCHMARK.json resolves to the files the harness reads."""

import dataclasses
import json
import re
from pathlib import Path

import pytest
from chipbench import config, harness, program
from chipbench.traffic import Traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/tpu"]
    script = ROOT / BENCH["command"][1]
    assert script.is_file() and HERE in script.parents
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = harness.load_cell(cell)
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert c.cfg["name"] == w["config"]
    assert isinstance(c.traffic, Traffic)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer


# the model fields that every configuration's file states, and that the
# harness compares with the program's own
STATED = ("n_tables", "n_dense", "embed_dim", "n_rows", "lookups", "bot_mlp",
          "top_mlp", "interaction")


def holds_what_runs(cfg: dict) -> None:
    """What the harness builds from the file ``cfg`` is what the file
    states. ``program.deployment`` compares each field of the program's
    model configuration with the file's key of that name, each per-table
    size table by table, and raises naming the keys that differ or that the
    program cannot take."""
    _, model = program.deployment(cfg)
    fields = {f.name for f in dataclasses.fields(model)}
    assert set(STATED) <= fields & set(cfg)
    for key in program.PER_TABLE:
        assert program._per_table(model.n_tables, getattr(model, key)) \
            == config.per_table(cfg, key)
    assert config.table_dtype(cfg).name == cfg["dtype"]
    assert callable(config.reference(cfg).logits)
    assert 0 < cfg["logit_gap_limit"] < 1


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_runs(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert set(entry["reduced"]) <= set(cfg) and len(entry["source"]) <= 200
    holds_what_runs(cfg)


@dataclasses.dataclass(frozen=True)
class _Model:
    """A model configuration with a vocabulary and a pooling per table."""

    name: str
    n_tables: int
    n_dense: int
    embed_dim: int
    n_rows: tuple
    lookups: tuple
    bot_mlp: tuple
    top_mlp: tuple
    interaction: str


def _stand_in(sizes, pools: str):
    """``from_arch`` and ``arch_model_config`` of a program that builds the
    model its arguments state, each per-table size annotated ``sizes``;
    with ``pools="first"`` every table pools as the first does."""

    def from_arch(cls, arch: str, *, n_tables: int, n_rows: sizes,
                  lookups: sizes, n_dense: int, embed_dim: int,
                  bot_mlp: list, top_mlp: list, interaction: str,
                  policies=(), batcher=None, **_deployment):
        def each(v):
            return tuple(v) if isinstance(v, list) else (v,) * n_tables
        pooling = each(lookups)
        if pools == "first":
            pooling = pooling[:1] * n_tables
        return _Model(arch, n_tables, n_dense, embed_dim, each(n_rows),
                      pooling, tuple(bot_mlp), tuple(top_mlp), interaction)

    return classmethod(from_arch), lambda model: model


@pytest.mark.parametrize("sizes, pools, refused", [
    (int | tuple[int, ...], "each", None),
    (int, "each", "the program takes one number for every table"),
    (int | tuple[int, ...], "first", "other sizes"),
], ids=["takes_per_table", "takes_one_number", "pools_as_the_first"])
def test_a_config_whose_tables_pool_differently_holds_what_runs(
        sizes, pools, refused, monkeypatch):
    """A file with a vocabulary and a pooling of its own for each table
    (``TINY``) passes the check of every configuration of the benchmark
    where the program takes and builds per-table sizes. Where it takes one
    number, or builds another pooling, the harness refuses the file and
    names ``lookups``."""
    import repro.serving as serving
    from test_bench_config import TINY

    from_arch, model_config = _stand_in(sizes, pools)
    monkeypatch.setattr(serving.DeploymentConfig, "from_arch", from_arch)
    monkeypatch.setattr(serving, "arch_model_config", model_config)
    if refused is None:
        holds_what_runs(TINY)
        return
    with pytest.raises(config.BenchError, match=refused) as e:
        holds_what_runs(TINY)
    assert "lookups" in str(e.value)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric["name"]))
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(metric["workloads"]) <= set(CELLS)
    assert metric["layer"] in (ROOT / "PERF.md").read_text()


def test_names_units_and_bounds():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({e["name"] for e in BENCH[group]}) == len(BENCH[group])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
