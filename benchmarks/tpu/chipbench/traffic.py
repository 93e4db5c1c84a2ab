"""Traffic for the chip benchmark: a pool of requests and an arrival schedule.

One traffic mix is one JSON file under ``traffic/``; this module is the one
generator that reads them. The key generator is a copy of
``repro.data.tracegen`` (Zipf ranks mapped through each table's popularity
permutation) with one change: the Zipf exponent is a number in the traffic
file, fixed by the configuration, and not calibrated to the length of the
run. ``calibrate_alpha`` is kept here so that the number in a file can be
checked against the rule it was set by.

Set-up draws a pool of distinct requests from the seed (indices and dense
features); the schedule then picks pool entries uniformly at random, as
MLPerf LoadGen does with its performance sample set. Every seed gives the
same amount of work: ``round(rate_rps * seconds)`` requests, placed by a
Poisson process conditioned on that count (sorted uniform times over the
window), or all due at the start of the window (``offline``).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
from chipbench.config import columns, index_shape

# copy of repro.data.tracegen.K_UNIQUE_RATE: locality knob -> unique-access rate
K_UNIQUE_RATE = {0.0: 0.08, 0.3: 0.22, 0.8: 0.37, 1.0: 0.51, 2.0: 0.66}
ARRIVALS = ("poisson", "offline")
# bins of the inverse-CDF guide table (``_zipf_ranks``)
_GUIDE_BITS = 20


def zipf_probs(n_rows: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n_rows + 1, dtype=np.float64) ** (-alpha)
    return w / w.sum()


def calibrate_alpha(n_rows: int, n_draws: int, target_rate: float) -> float:
    """The Zipf exponent at which ``n_draws`` draws hold ``target_rate``
    unique rows per draw (bisection, as ``tracegen.calibrate_alpha``)."""
    lo, hi = 0.0, 3.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        p = zipf_probs(n_rows, mid)
        rate = float((1.0 - np.exp(-n_draws * p)).sum()) / n_draws
        if rate > target_rate:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def popularity_perm(n_rows: int, table: int, pop_seed: int = 12345
                    ) -> np.ndarray:
    """Rank -> row id of one table, the ``tracegen.popularity_perm`` rule."""
    return np.random.default_rng(pop_seed + 7919 * table).permutation(n_rows)


@dataclasses.dataclass(frozen=True)
class Traffic:
    """One traffic mix, as its file states it."""

    name: str
    k: float                # locality knob the exponent was calibrated for
    alpha: float            # Zipf exponent of the keys
    arrivals: str           # "poisson" or "offline"
    rate_rps: float         # requests per second of the window
    pool: int               # distinct requests drawn at set-up
    pop_seed: int = 12345   # popularity permutations, as the deployment's

    def __post_init__(self) -> None:
        if self.arrivals not in ARRIVALS:
            raise ValueError(f"{self.name}: arrivals {self.arrivals!r} is "
                             f"not one of {ARRIVALS}")
        if self.rate_rps <= 0 or self.pool < 1:
            raise ValueError(f"{self.name}: rate_rps and pool must be "
                             f"positive")

    @classmethod
    def load(cls, path: Path) -> "Traffic":
        d = json.loads(Path(path).read_text())
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def n_requests(self, seconds: float) -> int:
        return max(1, int(round(self.rate_rps * seconds)))


def _zipf_sampler(n_rows: int, alpha: float):
    """Inverse-CDF draws of Zipf(alpha) ranks in ``[0, n_rows)``: a function
    of uniform ``u`` that gives ``searchsorted(cdf, u, side="right")``.

    A guide table holds the answer at each multiple of ``2**-_GUIDE_BITS``;
    where it is the same at both ends of ``u``'s bin, that is the answer, and
    only the other draws (the long tail) are searched in full.
    """
    cdf = np.cumsum(zipf_probs(n_rows, alpha))
    bins = 1 << _GUIDE_BITS
    guide = np.searchsorted(cdf, np.arange(bins + 1) / bins, side="right")
    settled = guide[:-1] == guide[1:]

    def ranks(u: np.ndarray) -> np.ndarray:
        j = (u * bins).astype(np.intp)
        out = guide[j]
        open_ = ~settled[j]
        out[open_] = np.searchsorted(cdf, u[open_], side="right")
        return np.minimum(out, n_rows - 1)
    return ranks


def make_pool(traffic: Traffic, n_rows: tuple[int, ...],
              lookups: tuple[int, ...], n_dense: int, seed: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """``(indices int32, dense (pool, n_dense) float32)``: for table ``t``,
    ``lookups[t]`` Zipf(alpha) ranks of its ``n_rows[t]`` rows through its
    popularity permutation, and dense features from N(0, 1). The indices
    are laid out as ``config.index_shape(lookups)`` says.

    The uniforms are drawn in one call, request by request and table by
    table; each vocabulary's CDF is built once and freed after use (a
    40M-row CDF is 320 MB of float64)."""
    rng = np.random.default_rng([seed, 0])
    u = rng.random((traffic.pool, sum(lookups)))
    indices = np.empty(u.shape, np.int32)
    cols = columns(lookups)
    for vocab in dict.fromkeys(n_rows):
        ranks = _zipf_sampler(vocab, traffic.alpha)
        for t in (t for t, n in enumerate(n_rows) if n == vocab):
            indices[:, cols[t]] = popularity_perm(vocab, t, traffic.pop_seed)[
                ranks(u[:, cols[t]])]
        del ranks
    dense = rng.standard_normal((traffic.pool, n_dense), dtype=np.float32)
    return indices.reshape((traffic.pool,) + index_shape(lookups)), dense


def make_schedule(traffic: Traffic, seconds: float, seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """``(arrival_us (n,) sorted float64, pool_ids (n,) int64)`` for one
    window of ``seconds``."""
    rng = np.random.default_rng([seed, 1])
    n = traffic.n_requests(seconds)
    if traffic.arrivals == "poisson":
        arrival_us = np.sort(rng.uniform(0.0, seconds * 1e6, n))
    else:
        arrival_us = np.zeros(n)
    return arrival_us, rng.integers(0, traffic.pool, n)
