"""Deployment API v2: config round-trip, facade behavior, multi-channel
lane invariants, and deprecation shims (DESIGN.md §3)."""

import json

import numpy as np
import pytest

from repro.core.engine import RecFlashEngine, TableSpec
from repro.flashsim.timeline import POLICIES, SERVING_POLICIES
from repro.serving import (BatcherConfig, Deployment, DeploymentConfig,
                           DynamicBatcher, RequestQueue, SLOConfig,
                           ServingScheduler, TriggerConfig,
                           arch_model_config, build_policy_engines)


def mk_config(n_tables=2, n_rows=5_000, lookups=8, **kw):
    kw.setdefault("policies", SERVING_POLICIES)
    return DeploymentConfig(
        tables=[TableSpec(n_rows, 64)] * n_tables, part="TLC",
        lookups=lookups, **kw)


class TestDeploymentConfig:
    def test_to_from_dict_round_trip_through_json(self):
        cfg = mk_config(seed=3, hot_frac=0.1, n_channels=4,
                        batcher=BatcherConfig(max_batch=16,
                                              max_wait_us=300.0),
                        trigger=TriggerConfig("threshold", top_frac=0.1,
                                              portion=0.002))
        blob = json.dumps(cfg.to_dict())
        cfg2 = DeploymentConfig.from_dict(json.loads(blob))
        assert cfg2 == cfg
        assert cfg2.to_dict() == cfg.to_dict()

    def test_part_normalized_and_validated(self):
        cfg = DeploymentConfig(tables=[TableSpec(100, 64)], part="qlc")
        assert cfg.part == "QLC"
        with pytest.raises(ValueError):
            DeploymentConfig(tables=[TableSpec(100, 64)], part="mlc")
        with pytest.raises(ValueError):
            DeploymentConfig(tables=[TableSpec(100, 64)],
                             policies=("nosuch",))
        with pytest.raises(ValueError):
            DeploymentConfig(tables=[TableSpec(100, 64)], n_channels=0)

    def test_from_arch_dlrm_rm2(self):
        cfg = DeploymentConfig.from_arch("dlrm_rm2", part="tlc")
        assert len(cfg.tables) == 26
        assert cfg.tables[0] == TableSpec(1_000_000, 64 * 4)
        assert cfg.lookups == 80
        assert cfg.part == "TLC"
        assert cfg.arch == "dlrm_rm2"
        assert cfg.policies == SERVING_POLICIES

    @pytest.mark.parametrize("name,n_tables,n_rows,widths", [
        # (n_dense, embed_dim, bot_mlp, top_mlp, lookups) of each source
        ("rmc1", 8, 1_000_000, (128, 32, (64, 32), (256, 64), 80)),
        ("rmc2", 32, 1_000_000, (256, 64, (128, 64), (128, 64), 120)),
        ("rmc3", 10, 1_000_000,
         (2560, 32, (1024, 256, 32), (512, 256), 20)),
        ("dlrm_small", 8, 50_000,
         (13, 64, (256, 128, 64), (256, 128), 20)),
        ("dlrm_rm2", 26, 1_000_000,
         (13, 64, (512, 256, 64), (512, 512, 256), 80)),
        # Criteo-1TB vocabularies (up to 40M rows) cut to 1M per table
        ("dlrm_mlperf", 26, 1_000_000,
         (13, 128, (512, 256, 128), (1024, 1024, 512, 256), 1)),
    ])
    def test_from_arch_model_has_the_source_widths(self, name, n_tables,
                                                   n_rows, widths):
        m = arch_model_config(DeploymentConfig.from_arch(name))
        assert (m.n_dense, m.embed_dim, m.bot_mlp, m.top_mlp,
                m.lookups) == widths
        assert m.n_tables == n_tables
        assert m.n_rows == (n_rows,) * n_tables

    def test_from_arch_overrides_and_unknown(self):
        cfg = DeploymentConfig.from_arch("rmc1", n_rows=10_000, n_tables=4,
                                         lookups=5, seed=9)
        assert len(cfg.tables) == 4
        assert cfg.tables[0].n_rows == 10_000
        assert cfg.lookups == 5 and cfg.seed == 9
        with pytest.raises(KeyError):
            DeploymentConfig.from_arch("nosuch-arch")

    def test_trigger_config_builds(self):
        from repro.core.triggers import PeriodTrigger, ThresholdTrigger
        assert isinstance(TriggerConfig("threshold").build(),
                          ThresholdTrigger)
        assert isinstance(TriggerConfig("period", period_days=2).build(),
                          PeriodTrigger)
        with pytest.raises(ValueError):
            TriggerConfig("never")

    def test_serving_policies_single_source(self):
        """The default policy tuple is the POLICIES-ordered serving subset."""
        assert SERVING_POLICIES == ("recssd", "rmssd", "recflash")
        assert list(SERVING_POLICIES) == [
            n for n in POLICIES if not n.startswith("recflash_")]
        from repro.launch import serve
        assert serve.POLICY_NAMES == SERVING_POLICIES


def mk_deployment(**kw):
    return Deployment(mk_config(**kw))


class TestDeploymentFacade:
    def test_one_engine_per_policy_sharing_stats(self):
        dep = mk_deployment()
        assert set(dep.engines) == set(SERVING_POLICIES)
        for eng in dep.engines.values():
            assert isinstance(eng, RecFlashEngine)
            assert eng.stats is dep.stats

    def test_run_stream_and_report(self):
        dep = mk_deployment(seed=4)
        reqs = dep.stream(48, 1000.0)
        traces = dep.run_stream(reqs)
        assert set(traces) == set(SERVING_POLICIES)
        rep = dep.report()
        assert rep["recflash"].n_requests == 48
        assert rep["recflash"].p99_us < rep["recssd"].p99_us

    def test_report_before_run_raises(self):
        with pytest.raises(RuntimeError):
            mk_deployment().report()

    def test_heterogeneous_tables_need_explicit_stats(self):
        cfg = DeploymentConfig(
            tables=[TableSpec(1000, 64), TableSpec(2000, 64)], lookups=4)
        with pytest.raises(ValueError):
            Deployment(cfg)

    def test_heterogeneous_tables_reject_stream(self):
        """stream() draws uniform-vocab rows; heterogeneous deployments must
        get a clear error instead of out-of-range row ids downstream."""
        from repro.core.freq import AccessStats
        stats = [AccessStats(np.zeros(n, dtype=np.int64))
                 for n in (1000, 500)]
        dep = Deployment(DeploymentConfig(
            tables=[TableSpec(1000, 64), TableSpec(500, 64)], lookups=4),
            sample_stats=stats)
        with pytest.raises(ValueError, match="uniform"):
            dep.stream(8, 1000.0)

    def test_step_day_serves_and_remaps(self):
        from repro.data.tracegen import generate_sls_batch
        dep = mk_deployment(policies=("rmssd", "recflash"),
                            trigger=TriggerConfig("period", period_days=1))
        tb, rows = generate_sls_batch(2, 5_000, 8, 64, k=0.0, seed=3)
        out = dep.step_day(0, tb, rows)
        assert out["rmssd"].remap is None          # baselines never charged
        assert out["recflash"].remap is not None   # period trigger fired
        assert out["recflash"].remap.remap_latency_us > 0
        assert out["recflash"].inference.latency_us \
            < out["rmssd"].inference.latency_us
        # windows are consumed by the trigger evaluation
        eng = dep.engines["recflash"]
        assert not any(eng.window_counts(t).any() for t in range(2))


class TestSingleChannelBitIdentical:
    def test_replay_matches_reference_single_server_loop(self):
        """n_channels=1 must reproduce the pre-refactor single-server path
        exactly: one coalesced command in service at a time, latency =
        completion - arrival."""
        cfg = mk_config(seed=11, batcher=BatcherConfig(max_batch=8,
                                                       max_wait_us=300.0))
        dep = Deployment(cfg)
        reqs = dep.stream(64, 2000.0, arrival="bursty")
        tr = dep.run_stream(reqs)["recflash"]

        ref_eng = Deployment(cfg).engines["recflash"]   # fresh device state
        batcher = DynamicBatcher(cfg.batcher)
        queue = RequestQueue(reqs)
        exp_lat = np.zeros(len(reqs))
        t_free = 0.0
        ref_eng.sim.reset_state()
        while len(queue):
            batch = batcher.next_batch(queue, device_free_us=t_free)
            start = max(batch.dispatch_us, t_free)
            svc = ref_eng.serve(batch.tables, batch.rows).latency_us
            t_free = start + svc
            for r in batch.requests:
                exp_lat[r.rid] = t_free - r.arrival_us
        np.testing.assert_array_equal(tr.latencies_us, exp_lat)

    def test_multi_channel_one_equals_default(self):
        dep = mk_deployment(seed=5)
        reqs = dep.stream(40, 1500.0)
        t1 = dep.run_stream(reqs)["recflash"]
        t1b = dep.run_stream(reqs, n_channels=1)["recflash"]
        np.testing.assert_array_equal(t1.latencies_us, t1b.latencies_us)


class TestMultiChannelLane:
    def mk_trace(self, n_channels, n=96, rate=20_000.0, seed=7):
        dep = mk_deployment(seed=seed,
                            batcher=BatcherConfig(max_batch=4,
                                                  max_wait_us=100.0))
        reqs = dep.stream(n, rate)
        tr = dep.run_stream(reqs, n_channels=n_channels)["recflash"]
        return reqs, tr

    def test_busy_time_conserved_and_channels_never_overlap(self):
        reqs, tr = self.mk_trace(4)
        assert sorted(set(tr.batch_channels.tolist())) == [0, 1, 2, 3]
        # per-batch service time = completion - start (all requests of one
        # batch complete together)
        per_channel_busy = np.zeros(4)
        last_free = np.zeros(4)
        total_busy = 0.0
        for b, c, start in zip(tr.batches, tr.batch_channels,
                               tr.batch_starts_us, strict=True):
            done = tr.completions_us[tr.index_of[b.requests[0].rid]]
            svc = done - start
            assert svc > 0
            # a channel services one command at a time
            assert start >= last_free[c] - 1e-9
            last_free[c] = done
            per_channel_busy[c] += svc
            total_busy += svc
        # accounting identity: lane busy == sum over channels, and the
        # report's utilisation is the per-channel mean of it
        assert total_busy == pytest.approx(per_channel_busy.sum())
        makespan = tr.completions_us.max() - min(r.arrival_us for r in reqs)
        assert tr.report.device_busy_frac == pytest.approx(
            total_busy / 4 / makespan)

    def test_no_request_served_before_arrival(self):
        reqs, tr = self.mk_trace(4)
        arrival = {r.rid: r.arrival_us for r in reqs}
        served = []
        for b, start in zip(tr.batches, tr.batch_starts_us, strict=True):
            for r in b.requests:
                assert start >= arrival[r.rid] - 1e-9
                served.append(r.rid)
        assert sorted(served) == sorted(arrival)   # each exactly once
        assert np.all(tr.latencies_us > 0)

    def test_more_channels_strictly_raise_saturated_throughput(self):
        """Assert on the cache-free rmssd lane: recflash's P$ is a per-
        controller budget *sliced* across channels, so on tiny tables the
        smaller per-channel cache can offset concurrency; rmssd isolates
        the channel-scaling effect itself (the benchmark-scale recflash
        win is checked in fig_serving_tail, see DESIGN.md §3.5)."""
        dep = mk_deployment(seed=2, batcher=BatcherConfig(max_batch=1,
                                                          max_wait_us=0.0))
        reqs = dep.stream(128, 50_000.0)          # far beyond 1-ch capacity
        thr = {}
        for nc in (1, 4):
            tr = dep.run_stream(reqs, n_channels=nc)["rmssd"]
            thr[nc] = tr.report.throughput_rps
        assert thr[4] > thr[1]

    def test_channel_sims_share_mappings_and_slice_cache(self):
        eng = mk_deployment().engines["recflash"]
        assert eng.channel_sims(1) == [eng.sim]   # exact single-server path
        sims = eng.channel_sims(4)
        assert all(s.mappings is eng.sim.mappings for s in sims)
        # the one controller P$ SRAM is sliced, not replicated, per channel
        assert all(s.cache_cfg.sram_bytes
                   == eng.sim.cache_cfg.sram_bytes // 4 for s in sims)


class TestDeprecatedShims:
    def test_build_policy_engines_warns_and_matches_deployment(self):
        with pytest.warns(DeprecationWarning):
            engines, stats = build_policy_engines(
                2, 5_000, 8, 64, "TLC", seed=0)
        dep = mk_deployment(seed=0)
        assert set(engines) == set(dep.engines)
        for t in range(2):
            np.testing.assert_array_equal(stats[t].counts,
                                          dep.stats[t].counts)

    def test_serving_scheduler_warns_and_matches_run_stream(self):
        dep = mk_deployment(seed=6)
        reqs = dep.stream(32, 1000.0)
        with pytest.warns(DeprecationWarning):
            sched = ServingScheduler(dep.engines,
                                     BatcherConfig(max_batch=8,
                                                   max_wait_us=200.0))
        old = sched.run(reqs)
        new = dep.run_stream(reqs, batcher=BatcherConfig(max_batch=8,
                                                         max_wait_us=200.0))
        for pol in dep.engines:
            np.testing.assert_array_equal(old[pol].latencies_us,
                                          new[pol].latencies_us)


class TestSLODeploymentConfig:
    """DeploymentConfig.slo (DESIGN.md §7): JSON round-trip, legacy-blob
    and from_arch defaulting, the live-remap exclusion, and the stream /
    run_stream plumbing."""

    def mk_slo(self):
        return SLOConfig(deadline_lc_us=1_500.0, deadline_std_us=9_000.0,
                         deadline_bulk_us=30_000.0, mix=(0.25, 0.5, 0.25),
                         bulk_chunk=4, headroom=0.75, shed_after=1.5,
                         degrade=False, lc_max_wait_us=50.0, ewma=0.5)

    def test_slo_round_trip_through_json(self):
        cfg = mk_config(seed=3, slo=self.mk_slo())
        blob = json.dumps(cfg.to_dict())
        cfg2 = DeploymentConfig.from_dict(json.loads(blob))
        assert cfg2 == cfg
        assert cfg2.slo == self.mk_slo()
        assert isinstance(cfg2.slo.mix, tuple)     # JSON list re-tupled
        assert cfg2.to_dict() == cfg.to_dict()

    def test_slo_none_and_legacy_blob_default_to_legacy_path(self):
        cfg = mk_config(seed=3)
        assert cfg.slo is None
        blob = cfg.to_dict()
        assert blob["slo"] is None
        assert DeploymentConfig.from_dict(blob).slo is None
        # a pre-SLO serialized config has no "slo" key at all; it must
        # deserialize to the legacy (slo=None) path, not raise
        legacy = {k: v for k, v in blob.items() if k != "slo"}
        cfg2 = DeploymentConfig.from_dict(legacy)
        assert cfg2.slo is None
        assert cfg2 == cfg

    def test_from_arch_slo_defaulting_and_override(self):
        assert DeploymentConfig.from_arch("rmc1").slo is None
        cfg = DeploymentConfig.from_arch("rmc1", slo=self.mk_slo())
        assert cfg.slo == self.mk_slo()

    def test_slo_and_live_remap_do_not_compose(self):
        from repro.serving import LiveRemapConfig
        with pytest.raises(ValueError, match="compose"):
            mk_config(trigger=TriggerConfig("threshold"),
                      live_remap=LiveRemapConfig(), slo=SLOConfig())
        dep = mk_deployment(seed=4, trigger=TriggerConfig("threshold"))
        reqs = dep.stream(8, 1000.0)
        with pytest.raises(ValueError, match="compose"):
            dep.run_stream(reqs, live=LiveRemapConfig(), slo=SLOConfig())

    def test_stream_annotates_classes_and_run_uses_slo_lane(self):
        from repro.serving import SLO_CLASSES
        slo = SLOConfig(mix=(0.3, 0.4, 0.3))
        dep = Deployment(mk_config(seed=9, policies=("recflash",),
                                   slo=slo))
        reqs = dep.stream(120, 2000.0)
        assert set(r.slo for r in reqs) == set(SLO_CLASSES)
        tr = dep.run_stream(reqs)["recflash"]
        assert set(tr.report.per_class) == set(SLO_CLASSES)
        assert tr.slo_classes is not None and tr.shed_mask is not None
        # same seed, no slo block: identical stream, default-class only,
        # and the legacy replay reports no per-class breakdown
        dep0 = Deployment(mk_config(seed=9, policies=("recflash",)))
        reqs0 = dep0.stream(120, 2000.0)
        assert all(r.slo == "standard" for r in reqs0)
        np.testing.assert_array_equal(
            np.array([r.arrival_us for r in reqs]),
            np.array([r.arrival_us for r in reqs0]))
        tr0 = dep0.run_stream(reqs0)["recflash"]
        assert tr0.report.per_class == {}
        assert tr0.slo_classes is None


class TestLaneTraceLatencyOf:
    def test_o1_lookup_and_keyerror(self):
        dep = mk_deployment(seed=8)
        reqs = dep.stream(20, 1000.0)
        tr = dep.run_stream(reqs)["recflash"]
        assert tr.latency_of(reqs[3].rid) == tr.latencies_us[3]
        # legacy two-arg call still works (second arg ignored)
        assert tr.latency_of(reqs[3].rid, reqs) == tr.latencies_us[3]
        with pytest.raises(KeyError):
            tr.latency_of(10_000)
