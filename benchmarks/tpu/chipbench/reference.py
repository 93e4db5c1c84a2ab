"""What every plain reference shares: matmuls at a named precision, and the
comparison of served logits with the reference's.

The model's own mathematics is in ``references/<name>.py``, the module a
configuration's file names (``config.reference``); each provides
``logits(cfg, weight_seed, indices, dense, precision, block)``, the plain
float32 ``jax.numpy`` forward, and the work counts ``flops_per_sample``,
``step_bytes`` and ``sls_work``. References import nothing of the program.

``precision="highest"`` is float32 matmuls (six bf16 passes on a TPU, exact
float32 products on the CPU). ``precision="high3"`` is the control: the same
computation with every matmul in the three-pass bfloat16 scheme that a TPU
runs for ``high`` precision, written out so that it reads the same on the
CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high3")
# what ``logit_gap`` reads for logits that are not finite (or not there):
# above any limit, and still a number in the result's JSON
NOT_A_NUMBER = float(np.finfo(np.float64).max)
HIGHEST = jax.lax.Precision.HIGHEST


def _split3(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def einsum(spec: str, a: jax.Array, b: jax.Array, precision: str
           ) -> jax.Array:
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    a_hi, a_lo = _split3(a)
    b_hi, b_lo = _split3(b)
    e = functools.partial(jnp.einsum, spec, precision=HIGHEST)
    return e(a_hi, b_hi) + (e(a_hi, b_lo) + e(a_lo, b_hi))


def logit_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between served and reference logits, as a share of
    the reference logits' root mean square. Non-finite logits read
    ``NOT_A_NUMBER``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return NOT_A_NUMBER
    rms = float(np.sqrt(np.mean(want * want)))
    return float(np.max(np.abs(got - want))) / rms
