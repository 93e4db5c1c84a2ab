"""Hypothesis property tests on the system's invariants."""

import numpy as np
import pytest

from repro.core.adaptive import AdaptiveHashTable
from repro.core.freq import AccessStats
from repro.core.remap import build_mapping
from repro.embedding.layout import RemapSpec
from repro.flashsim.device import PARTS, TIMING
from repro.flashsim.timeline import POLICIES, SLSSimulator

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st               # noqa: E402
from hypothesis import given, settings           # noqa: E402


@st.composite
def trace_case(draw):
    n_rows = draw(st.integers(64, 2048))
    n_acc = draw(st.integers(1, 400))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.default_rng(seed)
    rows = rng.zipf(draw(st.sampled_from([1.2, 1.5, 2.0])),
                    size=n_acc) % n_rows
    part = draw(st.sampled_from(sorted(PARTS)))
    policy = draw(st.sampled_from(sorted(POLICIES)))
    return n_rows, rows, part, policy


class TestSimulatorInvariants:
    @given(trace_case())
    @settings(max_examples=40, deadline=None)
    def test_vectorized_equals_exact_loop(self, case):
        """The fast path must be bit-identical to the stateful loop."""
        n_rows, rows, part_name, policy = case
        part = PARTS[part_name]
        stats = AccessStats.from_trace(rows, n_rows)
        pol = POLICIES[policy]
        m = build_mapping(n_rows, 128, part.page_bytes, part.n_planes,
                          mode=pol.mapping_mode, stats=stats)
        s1 = SLSSimulator(part, pol, [m], TIMING)
        s2 = SLSSimulator(part, pol, [m], TIMING)
        tb = np.zeros_like(rows)
        r1 = s1.run(tb, rows)
        r2 = s2.run(tb, rows, force_exact=True)
        assert r1.n_page_reads == r2.n_page_reads
        assert r1.n_buffer_hits == r2.n_buffer_hits
        assert r1.bytes_out == r2.bytes_out
        assert abs(r1.latency_us - r2.latency_us) < 1e-6 * max(
            1.0, r1.latency_us)

    @given(trace_case(), st.sampled_from([0, 1, 7, 64]),
           st.lists(st.integers(0, 400), min_size=2, max_size=2))
    @settings(max_examples=60, deadline=None)
    def test_fast_path_full_sweep(self, case, window, cuts):
        """Policy x part x window x seed sweep incl. the cached (P$) lane:
        per-call SimResults equal, carried buffer/drain/cache state equal
        across consecutive run() calls, and replace_mapping resets both
        paths identically (DESIGN.md §2.3)."""
        from repro.flashsim.device import CacheConfig

        n_rows, rows, part_name, policy = case
        part = PARTS[part_name]
        stats = AccessStats.from_trace(rows, n_rows)
        pol = POLICIES[policy]
        m = build_mapping(n_rows, 128, part.page_bytes, part.n_planes,
                          mode=pol.mapping_mode, stats=stats)
        s1 = SLSSimulator(part, pol, [m], TIMING, CacheConfig())
        s2 = SLSSimulator(part, pol, [m], TIMING, CacheConfig())
        n = rows.size
        lo, hi = sorted(min(c, n) for c in cuts)
        chunks = [rows[:lo], rows[lo:hi], rows[hi:]]
        for i, chunk in enumerate(chunks):
            tb = np.zeros_like(chunk)
            r1 = s1.run(tb, chunk, window=window)
            r2 = s2.run(tb, chunk, window=window, force_exact=True)
            assert (r1.n_lookups, r1.n_page_reads, r1.n_buffer_hits,
                    r1.n_cache_hits, r1.bytes_out) == \
                   (r2.n_lookups, r2.n_page_reads, r2.n_buffer_hits,
                    r2.n_cache_hits, r2.bytes_out), (policy, window, i)
            for f in ("latency_us", "energy_uj", "read_energy_uj"):
                a, b = getattr(r1, f), getattr(r2, f)
                assert abs(a - b) <= 1e-9 * max(1.0, abs(b)), (policy, f)
            np.testing.assert_array_equal(s1._buffer, s2._buffer)
            np.testing.assert_array_equal(s1._drain_pos, s2._drain_pos)
            if s1.cache is not None:
                assert s1.cache.residents() == s2.cache.residents()
                assert (s1.cache.hits, s1.cache.misses) == \
                       (s2.cache.hits, s2.cache.misses)
        # replace_mapping resets device + cache state on both paths
        s1.replace_mapping(0, m)
        s2.replace_mapping(0, m)
        tb = np.zeros_like(rows)
        r1 = s1.run(tb, rows, window=window)
        r2 = s2.run(tb, rows, window=window, force_exact=True)
        assert (r1.n_page_reads, r1.n_cache_hits, r1.bytes_out) == \
               (r2.n_page_reads, r2.n_cache_hits, r2.bytes_out)

    @given(st.integers(1, 40), st.integers(1, 60), st.integers(0, 2 ** 16),
           st.integers(0, 300))
    @settings(max_examples=60, deadline=None)
    def test_bulk_lru_matches_loop(self, n_slots, vocab, seed, n):
        """PageLRU.bulk_access == per-access loop: hit mask, final resident
        set/order, and hit/miss counters."""
        from repro.core.page_cache import PageLRU

        rng = np.random.default_rng(seed)
        pages = rng.integers(0, vocab, n)
        ref, vec = PageLRU(n_slots), PageLRU(n_slots)
        ref_hits = np.array([ref.access(int(p)) for p in pages], dtype=bool)
        vec_hits = vec.bulk_access(pages)
        np.testing.assert_array_equal(ref_hits, vec_hits)
        assert ref.residents() == vec.residents()
        assert (ref.hits, ref.misses) == (vec.hits, vec.misses)

    @given(trace_case())
    @settings(max_examples=40, deadline=None)
    def test_latency_lower_bound(self, case):
        """Latency >= #page-reads x t_R / n_planes (overlap cannot exceed
        plane parallelism) and energy >= reads x page energy."""
        n_rows, rows, part_name, policy = case
        part = PARTS[part_name]
        stats = AccessStats.from_trace(rows, n_rows)
        pol = POLICIES[policy]
        m = build_mapping(n_rows, 128, part.page_bytes, part.n_planes,
                          mode=pol.mapping_mode, stats=stats)
        sim = SLSSimulator(part, pol, [m], TIMING)
        r = sim.run(np.zeros_like(rows), rows)
        assert r.latency_us >= r.n_page_reads * part.t_r / part.n_planes
        assert r.energy_uj >= r.n_page_reads * part.e_page_read


class TestMappingInvariants:
    @given(st.integers(16, 4096), st.sampled_from(["baseline", "af",
                                                   "af_pd"]),
           st.integers(0, 2 ** 16))
    @settings(max_examples=40, deadline=None)
    def test_mapping_is_bijective(self, n_rows, mode, seed):
        rng = np.random.default_rng(seed)
        stats = AccessStats(rng.integers(0, 1000, n_rows).astype(np.int64))
        m = build_mapping(n_rows, 128, 4096, 2, mode=mode, stats=stats)
        assert sorted(m.perm.tolist()) == list(range(n_rows))
        keys = (m.page.astype(np.int64) * m.vectors_per_page
                + m.slot.astype(np.int64))
        assert len(set(keys.tolist())) == n_rows

    @given(st.integers(8, 2000), st.integers(1, 16), st.integers(0, 2 ** 16))
    @settings(max_examples=40, deadline=None)
    def test_remapspec_inverse(self, n_rows, n_shards, seed):
        rng = np.random.default_rng(seed)
        counts = rng.integers(0, 1000, n_rows)
        spec = RemapSpec.from_counts(counts, n_shards=n_shards)
        np.testing.assert_array_equal(spec.perm[spec.rank_of],
                                      np.arange(n_rows))
        np.testing.assert_array_equal(spec.rank_of[spec.perm],
                                      np.arange(n_rows))


class TestAdaptiveInvariants:
    @given(st.integers(10, 300), st.floats(0.02, 0.5),
           st.dictionaries(st.integers(0, 5000), st.integers(1, 10_000),
                           min_size=1, max_size=60),
           st.integers(0, 2 ** 16))
    @settings(max_examples=40, deadline=None)
    def test_update_invariants(self, n, hot_frac, window, seed):
        rng = np.random.default_rng(seed)
        freqs = np.sort(rng.integers(0, 10_000, n))[::-1]
        keys = rng.permutation(n) + 10_000        # disjoint from window keys
        ht = AdaptiveHashTable(keys=keys, freqs=freqs,
                               addrs=np.arange(n), hot_frac=hot_frac)
        hot_size = ht.hot_size
        ht.update(window)
        # 1) hot size invariant
        assert len(ht._hot) == hot_size
        # 2) hot prefix sorted descending by freq
        hf = [ht.freq_of(k) for k in ht.hot_keys()]
        assert hf == sorted(hf, reverse=True)
        # 3) addresses unique
        ht.compact()
        addrs = [ht.addr_of(k) for k in ht.keys_in_order()]
        assert len(set(addrs)) == len(addrs)
        # 4) no key lost
        assert len(ht) == n + len(window)
