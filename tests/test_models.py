"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
output shapes + no NaNs. One test class per assigned recommender or GNN
model.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.models import bert4rec, din, dlrm, graphsage
from repro.models.dlrm import RMC1


def _finite(x):
    return bool(jnp.isfinite(x).all())


# ------------------------------------------------------------- RecSys -----
class TestDLRM:
    def setup_method(self):
        self.cfg = dataclasses.replace(
            RMC1, n_rows=(500,) * RMC1.n_tables, lookups=4)
        self.params = dlrm.init(jax.random.PRNGKey(0), self.cfg)

    def _batch(self, b=8):
        return {
            "dense": jax.random.normal(jax.random.PRNGKey(1),
                                       (b, self.cfg.n_dense)),
            "indices": jax.random.randint(
                jax.random.PRNGKey(2),
                (b, self.cfg.n_tables, self.cfg.lookups), 0, 500, jnp.int32),
            "labels": jax.random.bernoulli(
                jax.random.PRNGKey(3), 0.3, (8,)).astype(jnp.float32),
        }

    def test_forward_shapes(self):
        logits = dlrm.forward(self.params, self._batch(), self.cfg)
        assert logits.shape == (8,)
        assert _finite(logits)

    def test_train_step_decreases_loss(self):
        batch = self._batch()
        opt = optim.partitioned(
            lambda ks: "table" if "tables" in ks else "dense",
            {"table": optim.adagrad(0.1, rowwise=True),
             "dense": optim.adamw(1e-2)})
        params, state = self.params, None
        state = opt.init(params)
        losses = []
        for _ in range(5):
            loss, grads = jax.value_and_grad(
                lambda p: dlrm.loss(p, batch, self.cfg))(params)
            params, state = opt.update(grads, state, params)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_remap_preserves_semantics(self):
        """Storing the table frequency-remapped must not change outputs."""
        from repro.embedding.layout import RemapSpec, remap_table
        batch = self._batch()
        base = dlrm.forward(self.params, batch, self.cfg)
        rng = np.random.default_rng(0)
        specs = [RemapSpec.from_counts(rng.integers(0, 100, v))
                 for v in self.cfg.n_rows]
        stored = dict(self.params)
        stored["tables"] = [remap_table(t, s)
                            for t, s in zip(self.params["tables"], specs, strict=True)]
        stored = dlrm.add_remap(
            stored, [jnp.asarray(s.rank_of) for s in specs])
        out = dlrm.forward(stored, batch, self.cfg)
        np.testing.assert_allclose(out, base, atol=1e-5)

    def test_retrieval_score(self):
        batch = {
            "dense": jax.random.normal(jax.random.PRNGKey(1),
                                       (1, self.cfg.n_dense)),
            "indices": jax.random.randint(
                jax.random.PRNGKey(2),
                (1, self.cfg.n_tables, self.cfg.lookups), 0, 500, jnp.int32),
            "candidates": jnp.arange(100, dtype=jnp.int32),
        }
        scores = dlrm.retrieval_score(self.params, batch, self.cfg)
        assert scores.shape == (100,)
        assert _finite(scores)

    def test_rmc_configs_match_table2(self):
        from repro.models.dlrm import RMC2, RMC3
        assert RMC1.n_tables == 8 and RMC1.embed_dim == 32
        assert RMC1.lookups == 80
        assert RMC2.n_tables == 32 and RMC2.embed_dim == 64
        assert RMC3.bot_mlp == (1024, 256, 32)


class TestDIN:
    def setup_method(self):
        self.cfg = din.DINConfig(n_items=1000, seq_len=20)
        self.params = din.init(jax.random.PRNGKey(0), self.cfg)

    def _batch(self, b=8):
        return {
            "hist": jax.random.randint(jax.random.PRNGKey(1),
                                       (b, 20), 0, 1000, jnp.int32),
            "hist_mask": jnp.ones((b, 20), bool).at[:, 15:].set(False),
            "target": jax.random.randint(jax.random.PRNGKey(2), (b,), 0,
                                         1000, jnp.int32),
            "profile": jax.random.normal(jax.random.PRNGKey(3), (b, 8)),
            "labels": jnp.ones((b,), jnp.float32),
        }

    def test_forward_and_grad(self):
        batch = self._batch()
        loss, grads = jax.value_and_grad(
            lambda p: din.loss(p, batch, self.cfg))(self.params)
        assert _finite(loss)
        assert all(_finite(g) for g in jax.tree.leaves(grads))

    def test_masked_history_ignored(self):
        batch = self._batch()
        out1 = din.forward(self.params, batch, self.cfg)
        # corrupt masked positions: output must not change
        hist2 = batch["hist"].at[:, 15:].set(7)
        out2 = din.forward(self.params, {**batch, "hist": hist2}, self.cfg)
        np.testing.assert_allclose(out1, out2, rtol=1e-5)

    def test_retrieval(self):
        b = {"hist": jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0,
                                        1000, jnp.int32),
             "hist_mask": jnp.ones((1, 20), bool),
             "profile": jax.random.normal(jax.random.PRNGKey(2), (1, 8)),
             "candidates": jnp.arange(50, dtype=jnp.int32)}
        scores = din.retrieval_score(self.params, b, self.cfg)
        assert scores.shape == (50,) and _finite(scores)


class TestBert4Rec:
    def setup_method(self):
        self.cfg = bert4rec.Bert4RecConfig(n_items=500, seq_len=24)
        self.params = bert4rec.init(jax.random.PRNGKey(0), self.cfg)

    def test_cloze_loss_and_grad(self):
        b, m = 4, 4
        batch = {
            "items": jax.random.randint(jax.random.PRNGKey(1), (b, 24), 1,
                                        500, jnp.int32),
            "pad_mask": jnp.ones((b, 24), bool),
            "mask_pos": jnp.tile(jnp.array([2, 7, 11, 19]), (b, 1)),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (b, m), 1,
                                          500, jnp.int32),
            "target_mask": jnp.ones((b, m), bool),
        }
        loss, grads = jax.value_and_grad(
            lambda p: bert4rec.loss(p, batch, self.cfg))(self.params)
        assert _finite(loss) and loss > 0
        assert all(_finite(g) for g in jax.tree.leaves(grads))

    def test_score_shapes(self):
        batch = {
            "items": jax.random.randint(jax.random.PRNGKey(3), (4, 24), 1,
                                        500, jnp.int32),
            "pad_mask": jnp.ones((4, 24), bool),
        }
        s = bert4rec.score(self.params, batch, self.cfg)
        assert s.shape == (4, 500) and _finite(s)

    def test_bidirectional_attention(self):
        """Future positions influence earlier scores (encoder, not causal)."""
        batch = {
            "items": jnp.ones((1, 24), jnp.int32),
            "pad_mask": jnp.ones((1, 24), bool),
        }
        h1 = bert4rec.encode(self.params, batch["items"],
                             batch["pad_mask"], self.cfg)
        items2 = batch["items"].at[0, -1].set(42)
        h2 = bert4rec.encode(self.params, items2, batch["pad_mask"],
                             self.cfg)
        assert float(jnp.abs(h1[0, 0] - h2[0, 0]).max()) > 0


# ---------------------------------------------------------------- GNN -----
class TestGraphSAGE:
    def test_full_graph(self):
        cfg = graphsage.SAGEConfig(d_in=16, n_classes=4)
        params = graphsage.init(jax.random.PRNGKey(0), cfg)
        n, e = 50, 200
        rng = np.random.default_rng(0)
        batch = {
            "feats": jnp.asarray(rng.normal(size=(n, 16)), jnp.float32),
            "edge_src": jnp.asarray(rng.integers(0, n, e), jnp.int32),
            "edge_dst": jnp.asarray(rng.integers(0, n, e), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 4, n), jnp.int32),
            "train_mask": jnp.ones((n,), jnp.float32),
        }
        loss, grads = jax.value_and_grad(
            lambda p: graphsage.loss_node(p, batch, cfg, "full"))(params)
        assert _finite(loss)
        assert all(_finite(g) for g in jax.tree.leaves(grads))

    def test_sampled_blocks_pipeline(self):
        from repro.data.sampler import CSRGraph, sample_blocks
        cfg = graphsage.SAGEConfig(d_in=8, n_classes=3, fanouts=(4, 3))
        params = graphsage.init(jax.random.PRNGKey(0), cfg)
        g = CSRGraph.random(100, avg_degree=5, d_feat=8, n_classes=3)
        rng = np.random.default_rng(1)
        blocks = sample_blocks(g, np.arange(16), (4, 3), rng)
        blocks = jax.tree.map(jnp.asarray, blocks)
        logits = graphsage.forward_sampled(params, blocks, cfg)
        assert logits.shape == (16, 3) and _finite(logits)

    def test_sampled_matches_full_when_fanout_covers(self):
        """With fanout >= max degree and deterministic neighbors the sampled
        estimator equals the full-graph forward (mean aggregator)."""
        cfg = graphsage.SAGEConfig(d_in=4, n_classes=2, fanouts=(50, 50))
        params = graphsage.init(jax.random.PRNGKey(0), cfg)
        # deterministic small graph: ring, each node one in-neighbor
        n = 10
        src = np.arange(n)
        dst = (np.arange(n) + 1) % n
        feats = np.random.default_rng(2).normal(size=(n, 4)).astype(
            np.float32)
        full = graphsage.forward_full(
            params, jnp.asarray(feats), jnp.asarray(src), jnp.asarray(dst),
            cfg)
        from repro.data.sampler import CSRGraph, sample_blocks
        g = CSRGraph.from_edges(n, src, dst, feats, np.zeros(n, np.int64))
        blocks = sample_blocks(g, np.arange(n), (1, 1),
                               np.random.default_rng(0))
        # degree-1 graph: sampling with fanout 1 IS the full neighborhood
        blocks = jax.tree.map(jnp.asarray, blocks)
        sampled = graphsage.forward_sampled(params, blocks, cfg)
        np.testing.assert_allclose(sampled, full, atol=1e-5)

    def test_batched_molecule_graphs(self):
        cfg = graphsage.SAGEConfig(d_in=6, n_classes=2)
        params = graphsage.init(jax.random.PRNGKey(0), cfg)
        b, n, e = 8, 10, 16
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(b, n, 6)), jnp.float32)
        edges = jnp.asarray(rng.integers(0, n, (b, e, 2)), jnp.int32)
        emask = jnp.ones((b, e), bool)
        nmask = jnp.ones((b, n), bool)
        out = graphsage.forward_batched_graphs(params, x, edges, emask,
                                               nmask, cfg)
        assert out.shape == (8, 2) and _finite(out)
