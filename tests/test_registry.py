"""Arch registry completeness: the assigned recommender and GNN archs (+ the
paper's RMCs) are selectable, each with the full shape-cell set and
metadata."""

import pytest

from repro.configs.base import get_arch, list_archs

ASSIGNED = ["graphsage-reddit", "din", "dlrm-mlperf", "dlrm-rm2", "bert4rec"]
RMC = ["rmc1", "rmc2", "rmc3"]

GNN_SHAPES = {"full_graph_sm", "minibatch_lg", "ogb_products", "molecule"}
REC_SHAPES = {"train_batch", "serve_p99", "serve_bulk", "retrieval_cand"}


class TestRegistry:
    def test_all_assigned_archs_registered(self):
        archs = list_archs()
        for name in ASSIGNED + RMC:
            assert name in archs, name

    @pytest.mark.parametrize("name", ASSIGNED + RMC)
    def test_bundle_has_full_cell_set(self, name):
        b = get_arch(name)
        expect = {"gnn": GNN_SHAPES, "recsys": REC_SHAPES}[b.family]
        assert set(b.steps) == expect, (name, set(b.steps))
        for shape, step in b.steps.items():
            assert callable(step.make_fn), (name, shape)
        assert set(b.model_flops) == expect, name
        assert all(f > 0 for f in b.model_flops.values()), name
        assert callable(b.init)
        assert b.optimizer is not None

    def test_assigned_configs_match_spec(self):
        """Spot-check the exact assigned hyper-parameters."""
        dl = get_arch("dlrm-mlperf").cfg
        assert dl.n_tables == 26 and dl.embed_dim == 128
        assert dl.bot_mlp[-1] == 128 and dl.top_mlp[0] == 1024
        gs = get_arch("graphsage-reddit").cfg
        assert gs.n_layers == 2 and gs.d_hidden == 128
        assert gs.aggregator == "mean"
        dn = get_arch("din").cfg
        assert (dn.embed_dim, dn.seq_len, dn.attn_mlp, dn.mlp) == \
            (18, 100, (80, 40), (200, 80))
        b4 = get_arch("bert4rec").cfg
        assert (b4.embed_dim, b4.n_blocks, b4.n_heads, b4.seq_len) == \
            (64, 2, 2, 200)
