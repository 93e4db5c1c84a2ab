"""Every entry of BENCHMARK.json resolves to the files the harness reads."""

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


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_runs(entry):
    from repro.serving import DeploymentConfig, arch_model_config

    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert set(entry["reduced"]) <= set(cfg) and len(entry["source"]) <= 200
    model = arch_model_config(DeploymentConfig.from_arch(cfg["arch"]))
    assert (model.n_tables, model.n_dense, model.embed_dim,
            list(model.bot_mlp), list(model.top_mlp), model.interaction) \
        == (cfg["n_tables"], cfg["n_dense"], cfg["embed_dim"],
            cfg["bot_mlp"], cfg["top_mlp"], cfg["interaction"])
    assert tuple(model.n_rows) == config.per_table(cfg, "n_rows")
    assert (model.lookups,) * model.n_tables \
        == config.per_table(cfg, "lookups")
    # and what the harness builds from the file is what the file states
    assert program.deployment(cfg)[1] == model
    assert config.table_dtype(cfg).name == cfg["dtype"]
    assert callable(config.reference(cfg).logits)
    assert 0 < cfg["logit_gap_limit"] < 1


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
