"""The benchmark's yardstick: operation and byte counts, and the peaks table.

The counts are each configuration's reference module's
(``config.reference``); those of the DLRM reference are checked against the
arithmetic and parameters of the program the harness builds from the file
(``program.deployment``).
"""

import json
from pathlib import Path

import numpy as np
import pytest
from chipbench import config, program, yardstick

HERE = Path(__file__).resolve().parent
CONFIGS = sorted((HERE / "configs").glob("*.json"))
DLRM = [p for p in CONFIGS
        if json.loads(p.read_text()).get("reference") == "dlrm"]


def _cfg(path: Path) -> dict:
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", DLRM, ids=lambda p: p.stem)
def test_flops_are_the_programs_less_what_is_not_required(path):
    cfg = _cfg(path)
    _, model = program.deployment(cfg)
    d, n = cfg["embed_dim"], cfg["n_tables"] + 1
    not_required = (2 * d * d                       # no embed_dim->embed_dim layer
                    + 2 * n * n * d - n * (n - 1) * d   # lower triangle, diagonal
                    + sum(config.per_table(cfg, "lookups")) * d)  # SLS mul
    assert config.reference(cfg).flops_per_sample(cfg) \
        == model.flops_per_sample() - not_required


@pytest.mark.parametrize("path", DLRM, ids=lambda p: p.stem)
def test_mlp_weight_bytes_match_the_programs_parameters(path):
    import jax

    import repro.models.dlrm as dlrm

    cfg = _cfg(path)
    _, model = program.deployment(dict(cfg, n_rows=8))
    params = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), model))
    mlp = [leaf for key in ("bot", "top")
           for leaf in jax.tree.leaves(params[key])]
    assert config.reference(cfg).mlp_weight_bytes(cfg) \
        == sum(4 * x.size for x in mlp)


def test_step_bytes_count_distinct_rows_once_per_table():
    cfg = {"name": "tiny", "reference": "dlrm", "dtype": "float32",
           "n_tables": 2, "n_rows": 10, "n_dense": 3, "embed_dim": 4,
           "lookups": 3, "bot_mlp": [4], "top_mlp": [2], "interaction": "dot"}
    reference = config.reference(cfg)
    # request 0: table 0 rows {5, 5, 7}, table 1 rows {1, 2, 3}
    # request 1: table 0 rows {7, 8, 5}, table 1 rows {3, 3, 3}
    indices = np.array([[[5, 5, 7], [1, 2, 3]],
                        [[7, 8, 5], [3, 3, 3]]])
    assert yardstick.distinct_rows(indices, (3, 3)) == 3 + 3
    # bottom 3->4 (12 + 4), top 5->2->1 (10 + 2, 2 + 1); top_in = 4 + 3
    weights = 4 * (3 * 4 + 4 + 7 * 2 + 2 + 2 * 1 + 1)
    assert reference.mlp_weight_bytes(cfg) == weights
    want = (6 * 4 * 4          # distinct rows x embed_dim x 4 B
            + weights
            + 2 * 3 * 4        # dense features of two requests
            + 2 * 2 * 3 * 4    # their indices
            + 2 * 4)           # their logits
    assert reference.step_bytes(cfg, indices) == want


def test_least_time_names_the_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert yardstick.least_time_s(50.0, 20.0, peaks) == (2.0, "bytes")
    assert yardstick.least_time_s(500.0, 20.0, peaks) == (5.0, "flops")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        yardstick.load_peaks(HERE / "peaks.json", "TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    peaks = yardstick.load_peaks(HERE / "peaks.json", "TPU v5 lite")
    assert peaks == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                     "hbm_bytes": 16e9}
