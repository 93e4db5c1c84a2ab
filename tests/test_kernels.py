"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps per kernel; assert_allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.dot_interaction import dot_interaction as dot_raw
from repro.kernels.recflash_sls import recflash_sls as sls_raw


def _inputs(h, v, d, b, lk, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    hot = jax.random.normal(k1, (h, d), dtype)
    cold = jax.random.normal(k2, (v - h, d), dtype)
    idx = jax.random.randint(k3, (b, lk), 0, v, jnp.int32)
    return hot, cold, idx


class TestRecFlashSLS:
    @pytest.mark.parametrize("h,v,d,b,lk", [
        (32, 128, 8, 16, 4),
        (64, 512, 16, 32, 20),
        (16, 64, 32, 8, 1),       # single lookup per bag
        (128, 130, 64, 8, 7),     # nearly-all-hot table
    ])
    def test_shapes_vs_oracle(self, h, v, d, b, lk):
        hot, cold, idx = _inputs(h, v, d, b, lk, jnp.float32)
        out = sls_raw(hot, cold, idx, block_b=8, interpret=True)
        ref = ops.sls_ref(hot, cold, idx)
        # the kernel accumulates its bag sequentially (fori_loop) while the
        # oracle reduces pairwise — f32 sums of L terms legitimately differ
        # by O(L*eps), so the bound is 1e-5, not bit-level 1e-6
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                            (jnp.bfloat16, 2e-2)])
    def test_dtypes(self, dtype, rtol):
        hot, cold, idx = _inputs(32, 256, 16, 16, 8, dtype)
        out = sls_raw(hot, cold, idx, block_b=8, interpret=True)
        ref = ops.sls_ref(hot, cold, idx)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), rtol=rtol,
                                   atol=1e-6)

    def test_all_hot_and_all_cold_paths(self):
        hot, cold, _ = _inputs(32, 64, 8, 8, 4, jnp.float32)
        idx_hot = jnp.zeros((8, 4), jnp.int32)               # hot row 0
        idx_cold = jnp.full((8, 4), 40, jnp.int32)           # cold row
        for idx in (idx_hot, idx_cold):
            out = sls_raw(hot, cold, idx, block_b=8, interpret=True)
            np.testing.assert_allclose(out, ops.sls_ref(hot, cold, idx),
                                       rtol=1e-6)

    def test_block_b_must_divide(self):
        hot, cold, idx = _inputs(32, 64, 8, 10, 4, jnp.float32)
        with pytest.raises(ValueError):
            sls_raw(hot, cold, idx, block_b=8, interpret=True)

    def test_jitted_wrapper(self):
        hot, cold, idx = _inputs(32, 128, 8, 16, 4, jnp.float32)
        out = ops.recflash_sls(hot, cold, idx)
        np.testing.assert_allclose(out, ops.sls_ref(hot, cold, idx),
                                   rtol=1e-5, atol=1e-6)

    def test_hot_tier_over_vmem_budget_raises(self):
        """The pinned hot tier is checked against the VMEM budget before
        anything is built (shapes only, nothing allocated)."""
        from repro.kernels.recflash_sls import HOT_VMEM_BUDGET
        d = 128
        h = HOT_VMEM_BUDGET // (d * 4) + 8
        args = (jax.ShapeDtypeStruct((h, d), jnp.bfloat16),
                jax.ShapeDtypeStruct((4 * h, d), jnp.bfloat16),
                jax.ShapeDtypeStruct((8, 4), jnp.int32))
        with pytest.raises(ValueError, match=str(HOT_VMEM_BUDGET)):
            jax.eval_shape(
                lambda a, b, c: sls_raw(a, b, c, interpret=True), *args)

    @pytest.mark.parametrize("d", [48, 192])
    def test_dim_must_tile_the_lanes(self, d):
        hot, cold, idx = _inputs(8, 32, d, 8, 2, jnp.float32)
        with pytest.raises(ValueError, match="divide 128"):
            sls_raw(hot, cold, idx, block_b=8, interpret=True)

    def test_uneven_tiers_are_padded_to_whole_lines(self):
        """H and V-H that are not multiples of the rows per lane line."""
        hot, cold, idx = _inputs(7, 70, 32, 8, 9, jnp.float32)
        out = sls_raw(hot, cold, idx, block_b=8, interpret=True)
        np.testing.assert_allclose(out, ops.sls_ref(hot, cold, idx),
                                   rtol=1e-5, atol=1e-6)

    def test_interpret_only_on_cpu(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()


class TestDotInteraction:
    @pytest.mark.parametrize("b,t,d", [(64, 9, 16), (128, 27, 64),
                                       (64, 33, 128), (8, 3, 18)])
    def test_shapes_vs_oracle(self, b, t, d):
        z = jax.random.normal(jax.random.PRNGKey(0), (b, t, d))
        gram = dot_raw(z, block_b=min(64, b), interpret=True)
        np.testing.assert_allclose(gram, ops.dot_ref(z), rtol=1e-5)

    def test_triangle_extraction(self):
        z = jax.random.normal(jax.random.PRNGKey(1), (16, 5, 8))
        flat = ops.dot_interaction(z)
        assert flat.shape == (16, 10)      # 5C2
        gram = ops.dot_ref(z)
        iu, ju = np.triu_indices(5, k=1)
        np.testing.assert_allclose(flat, gram[:, iu, ju], rtol=1e-5)

    @pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                            (jnp.bfloat16, 3e-2)])
    def test_dtypes(self, dtype, rtol):
        z = jax.random.normal(jax.random.PRNGKey(2), (32, 9, 32), dtype)
        gram = dot_raw(z, block_b=32, interpret=True)
        np.testing.assert_allclose(np.asarray(gram, np.float32),
                                   np.asarray(ops.dot_ref(z), np.float32),
                                   rtol=rtol)
