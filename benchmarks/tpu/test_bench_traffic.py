"""The benchmark's traffic: a fixed skew, a pool and a schedule from the seed."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest
from chipbench import config
from chipbench import traffic as tr

HERE = Path(__file__).resolve().parent
TRAFFIC = sorted((HERE / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 977


def _small(path: Path, **kw) -> tr.Traffic:
    return dataclasses.replace(tr.Traffic.load(path), pool=64, **kw)


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_alpha_is_the_deployments_offline_calibration(path):
    from repro.data.tracegen import calibrate_alpha

    t = tr.Traffic.load(path)
    cfg = json.loads((HERE / "configs" /
                      f"{json.loads(path.read_text())['config']}.json")
                     .read_text())
    n_rows = set(config.per_table(cfg, "n_rows"))
    lookups = set(config.per_table(cfg, "lookups"))
    if len(n_rows) > 1 or len(lookups) > 1:
        # the offline sample's rule is for one vocabulary and one pooling;
        # a file with several states how it set its exponent
        assert 0 < t.alpha < 3 and json.loads(path.read_text())["alpha_from"]
        return
    (n_rows,), (lookups,) = n_rows, lookups
    draws = cfg["sample_inferences"] * lookups
    want = tr.calibrate_alpha(n_rows, draws, tr.K_UNIQUE_RATE[t.k])
    assert t.alpha == want
    assert want == calibrate_alpha(n_rows, draws,
                                   tr.K_UNIQUE_RATE[t.k])


def test_pool_is_fixed_by_the_seed_and_not_by_rate_or_length():
    path = HERE / "traffic" / "rm2.zipf_hot_poisson.json"
    sizes = ((1000,) * 3, (5,) * 3)
    a = tr.make_pool(_small(path), *sizes, 4, BIG_SEED)
    b = tr.make_pool(_small(path, rate_rps=7.0), *sizes, 4, BIG_SEED)
    c = tr.make_pool(_small(path), *sizes, 4, BIG_SEED + 1)
    for x, y in zip(a, b, strict=True):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.int32 and a[1].dtype == np.float32
    assert a[0].shape == (64, 3, 5) and a[1].shape == (64, 4)
    assert 0 <= a[0].min() and a[0].max() < 1000


def test_schedule_gives_every_seed_the_same_work():
    path = HERE / "traffic" / "rm2.zipf_hot_poisson.json"
    t = _small(path, rate_rps=250.0)
    arr, ids = tr.make_schedule(t, 4.0, BIG_SEED)
    arr2, ids2 = tr.make_schedule(t, 4.0, BIG_SEED)
    other, _ = tr.make_schedule(t, 4.0, 5)
    assert arr.size == other.size == 1000
    np.testing.assert_array_equal(arr, arr2)
    np.testing.assert_array_equal(ids, ids2)
    assert not np.array_equal(arr, other)
    assert np.all(np.diff(arr) >= 0) and 0 <= arr[0] and arr[-1] < 4e6
    assert 0 <= ids.min() and ids.max() < t.pool
    off, _ = tr.make_schedule(dataclasses.replace(t, arrivals="offline"),
                              4.0, BIG_SEED)
    assert off.size == 1000 and not off.any()


def test_keys_follow_the_programs_popularity_convention():
    from repro.data.tracegen import popularity_perm

    for t in (0, 3):
        np.testing.assert_array_equal(tr.popularity_perm(1000, t),
                                      popularity_perm(1000, 12345 + 7919 * t))


def test_split_inverse_cdf_equals_the_plain_one():
    cdf = np.cumsum(tr.zipf_probs(50_000, 1.1))
    u = np.random.default_rng(3).random(20_000)
    ranks = tr._zipf_sampler(50_000, 1.1)(u)
    want = np.minimum(np.searchsorted(cdf, u, side="right"), cdf.size - 1)
    np.testing.assert_array_equal(ranks, want)
    assert ranks.max() > 1000


def test_hot_prefix_share_of_the_rm2_traffic():
    path = HERE / "traffic" / "rm2.zipf_hot_poisson.json"
    t = tr.Traffic.load(path)
    share = tr.zipf_probs(1_000_000, t.alpha)[:2000].sum()
    assert 0.94 < share < 0.96
