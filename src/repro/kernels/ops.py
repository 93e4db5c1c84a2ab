"""jit'd public wrappers around the Pallas kernels.

On TPU the kernels lower through Mosaic. On the CPU backend (tests, and
rehearsals without a chip) they run in ``interpret=True`` mode — the kernel
body executes exactly, just without the Mosaic compiler — so tests validate
the real kernel logic. Any other backend raises: a kernel never falls back
to the interpreter silently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.dot_interaction import dot_interaction as _dot_kernel
from repro.kernels.recflash_sls import recflash_sls as _sls_kernel


def _interpret() -> bool:
    """Compiled on TPU, interpreted on CPU; no other backend runs them."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on 'tpu' or "
                       f"interpreted on 'cpu'; the backend is {backend!r}")


@functools.partial(jax.jit, static_argnames=("block_b",))
def recflash_sls(hot, cold, indices, block_b: int = 8):
    """Two-tier SLS: hot (H,D) VMEM tier, cold (V-H,D) HBM tier,
    indices (B,L) ranks into [hot; cold] -> (B,D) float32 bag sums."""
    return _sls_kernel(hot, cold, indices, block_b=block_b,
                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_b",))
def dot_interaction(z, block_b: int = 64):
    """DLRM interaction: z (B,T,D) -> (B, T*(T-1)/2) upper-triangle dots."""
    gram = _dot_kernel(z, block_b=block_b, interpret=_interpret())
    t = z.shape[1]
    iu, ju = jnp.triu_indices(t, k=1)
    return gram[:, iu, ju]


# oracles re-exported for benchmarks/tests
sls_ref = _ref.recflash_sls_ref
dot_ref = _ref.dot_interaction_ref
