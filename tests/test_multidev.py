"""Multi-device semantics, run in subprocesses with 8 forced host devices
(the main test process must keep 1 device — see dryrun.py notes).

Covers: sharded masked-psum embedding bag vs dense oracle, the two-phase
remapped lookup, gradient compression with error feedback, and checkpoint
restore onto a different mesh.
"""

import os
import subprocess
import sys

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def run(script: str):
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.compat import make_mesh, shard_map
assert len(jax.devices()) == 8
mesh = make_mesh((2, 4), ("data", "model"))
"""


class TestShardedEmbedding:
    def test_masked_psum_bag_matches_dense(self):
        run(PREAMBLE + """
from repro.embedding.sharded import make_sharded_bag
from repro.embedding.bag import embedding_bag_dense
table = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
idx = jax.random.randint(jax.random.PRNGKey(1), (16, 5), 0, 64, jnp.int32)
fn = make_sharded_bag(mesh, P("model", None), P("data", None), P("data", None))
out = jax.jit(fn)(table, idx)
ref = embedding_bag_dense(table, idx)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
""")

    def test_two_phase_remapped_bag(self):
        run(PREAMBLE + """
from repro.embedding.sharded import sharded_remapped_bag
from repro.embedding.bag import embedding_bag_dense
from repro.embedding.layout import RemapSpec, remap_table
table = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
counts = np.random.default_rng(0).integers(0, 50, 64)
spec = RemapSpec.from_counts(counts, n_shards=4)
stored = remap_table(table, spec)
idx = jax.random.randint(jax.random.PRNGKey(1), (16, 5), 0, 64, jnp.int32)
fn = shard_map(
    lambda tb, ro, ix: sharded_remapped_bag(tb, ro, ix, "model"),
    mesh=mesh, in_specs=(P("model", None), P("model"), P("data", None)),
    out_specs=P("data", None), check_vma=False)
out = jax.jit(fn)(stored, jnp.asarray(spec.rank_of), idx)
ref = embedding_bag_dense(table, idx)   # logical-table oracle
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
""")

    def test_hlo_has_no_table_allgather(self):
        """The sharded bag must never all-gather the table."""
        run(PREAMBLE + """
from repro.embedding.sharded import make_sharded_bag
table = jax.ShapeDtypeStruct((1 << 14, 64), jnp.float32)
idx = jax.ShapeDtypeStruct((32, 8), jnp.int32)
fn = make_sharded_bag(mesh, P("model", None), P("data", None), P("data", None))
txt = jax.jit(fn).lower(table, idx).compile().as_text()
table_bytes = (1 << 14) * 64 * 4
import re
for line in txt.splitlines():
    if "all-gather" in line and "f32[" in line:
        m = re.search(r"f32\\[([0-9,]+)\\]", line)
        if m:
            n = 1
            for d in m.group(1).split(","): n *= int(d)
            assert n * 4 < table_bytes / 2, line
""")


class TestGradCompression:
    def test_compressed_psum_approximates_mean(self):
        run(PREAMBLE + """
from repro.distributed.compression import compressed_psum
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
def f(g):
    out, _ = compressed_psum(g, "data", None)
    return out
fn = shard_map(f, mesh=mesh, in_specs=P("data", None),
                   out_specs=P("data", None), check_vma=False)
out = jax.jit(fn)(g)
# reference: mean over the data shards of each shard's rows
ref = np.asarray(g).reshape(2, 4, 64).mean(0)
ref = np.tile(ref, (2, 1))
np.testing.assert_allclose(np.asarray(out), ref, atol=2e-2)
""")

    def test_error_feedback_reduces_bias(self):
        run(PREAMBLE + """
from repro.distributed.compression import compressed_psum, CompressionState
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.001
def step(g, res):
    st = CompressionState(residual=res)
    out, st2 = compressed_psum(g, "data", st, bits=4)
    return out, st2.residual
fn = shard_map(step, mesh=mesh,
                   in_specs=(P("data", None), P("data", None)),
                   out_specs=(P("data", None), P("data", None)),
                   check_vma=False)
res = jnp.zeros((8, 64))
acc = jnp.zeros((8, 64))
for _ in range(20):
    out, res = jax.jit(fn)(g, res)
    acc = acc + out
ref = np.asarray(g).reshape(2, 4, 64).mean(0)
ref = np.tile(ref, (2, 1)) * 20
# with error feedback, accumulated compressed sums track the true sum
np.testing.assert_allclose(np.asarray(acc), ref, atol=0.05 * abs(ref).max() + 1e-3)
""")


class TestElasticResharding:
    def test_restore_onto_different_mesh(self):
        run(PREAMBLE + """
import tempfile, os
from repro import checkpoint as ckpt
tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8))}
d = tempfile.mkdtemp()
# save from a (2,4) mesh sharding
sh1 = NamedSharding(mesh, P("data", "model"))
tree1 = jax.tree.map(lambda x: jax.device_put(x, sh1), tree)
ckpt.save(d, 1, tree1)
# restore onto a different mesh shape (4,2)
mesh2 = make_mesh((4, 2), ("data", "model"))
sh2 = {"w": NamedSharding(mesh2, P("model", "data"))}
out = ckpt.restore(d, 1, tree, sh2)
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
assert out["w"].sharding == sh2["w"]
""")


class TestDistributedDLRM:
    def test_sharded_forward_matches_local(self):
        run(PREAMBLE + """
import dataclasses
from repro.models import dlrm
cfg = dataclasses.replace(dlrm.RMC1, n_rows=(64,) * 8, lookups=4)
params = dlrm.init(jax.random.PRNGKey(0), cfg)
batch = {
  "dense": jax.random.normal(jax.random.PRNGKey(1), (8, cfg.n_dense)),
  "indices": jax.random.randint(jax.random.PRNGKey(2), (8, 8, 4), 0, 64,
                                jnp.int32),
}
local = dlrm.forward(params, batch, cfg)
out = jax.jit(lambda p, b: dlrm.forward(p, b, cfg, mesh))(params, batch)
np.testing.assert_allclose(np.asarray(out), np.asarray(local), atol=1e-4)
""")


class TestTable2D:
    def test_2d_bag_matches_dense_incl_grads(self):
        run(PREAMBLE + """
from repro.embedding.sharded import sharded_embedding_bag_2d
from repro.embedding.bag import embedding_bag_dense
from repro.embedding.layout import RemapSpec, remap_table
V, D, B, L = 64, 8, 16, 5
table = jax.random.normal(jax.random.PRNGKey(0), (V, D))
idx = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, V, jnp.int32)
fn = shard_map(lambda tb, ix: sharded_embedding_bag_2d(tb, ix),
                   mesh=mesh,
                   in_specs=(P(("model", "data"), None), P("data", None)),
                   out_specs=P(("data", "model"), None), check_vma=False)
ref = embedding_bag_dense(table, idx)
np.testing.assert_allclose(np.asarray(jax.jit(fn)(table, idx)),
                           np.asarray(ref), atol=1e-5)
# remapped two-phase variant
counts = np.random.default_rng(0).integers(0, 50, V)
spec = RemapSpec.from_counts(counts, n_shards=8)
stored = remap_table(table, spec)
fn2 = shard_map(lambda tb, ix, ro: sharded_embedding_bag_2d(tb, ix, ro),
                    mesh=mesh,
                    in_specs=(P(("model", "data"), None), P("data", None),
                              P(("model", "data"))),
                    out_specs=P(("data", "model"), None), check_vma=False)
np.testing.assert_allclose(
    np.asarray(jax.jit(fn2)(stored, idx, jnp.asarray(spec.rank_of))),
    np.asarray(ref), atol=1e-5)
# gradients flow shard-locally and match the dense oracle
g = jax.grad(lambda tb: jax.jit(fn)(tb, idx).sum())(table)
gref = jax.grad(lambda tb: embedding_bag_dense(tb, idx).sum())(table)
np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=1e-5)
""")

    def test_hybrid_sharded_forward_matches_local(self):
        """Hybrid (psum_scatter + batch-split dense) == plain forward."""
        run(PREAMBLE + """
import dataclasses
from repro.models import dlrm
cfg = dataclasses.replace(dlrm.RMC1, n_rows=(64,) * 8, lookups=4)
params = dlrm.init(jax.random.PRNGKey(0), cfg)
batch = {
  "dense": jax.random.normal(jax.random.PRNGKey(1), (8, cfg.n_dense)),
  "indices": jax.random.randint(jax.random.PRNGKey(2), (8, 8, 4), 0, 64,
                                jnp.int32),
  "labels": jax.random.bernoulli(jax.random.PRNGKey(3), 0.3,
                                 (8,)).astype(jnp.float32),
}
local = dlrm.forward(params, batch, cfg)
out = jax.jit(lambda p, b: dlrm.forward(p, b, cfg, mesh,
                                        hybrid=True))(params, batch)
np.testing.assert_allclose(np.asarray(out), np.asarray(local), atol=1e-4)
# the 2D table layout + hybrid, loss + grads
l_local = dlrm.loss(params, batch, cfg)
l_2d = jax.jit(lambda p, b: dlrm.loss(p, b, cfg, mesh, hybrid=True,
                                      table_2d=True))(params, batch)
np.testing.assert_allclose(np.asarray(l_2d), np.asarray(l_local),
                           atol=1e-5)
g_local = jax.grad(lambda p: dlrm.loss(p, batch, cfg))(params)
g_2d = jax.jit(jax.grad(
    lambda p: dlrm.loss(p, batch, cfg, mesh, hybrid=True,
                        table_2d=True)))(params)
for a, b in zip(jax.tree.leaves(g_2d), jax.tree.leaves(g_local)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
""")
