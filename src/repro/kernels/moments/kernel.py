"""Per-row streaming-moments Pallas kernel (stratified-sampling reduction).

``ZMCintegral_normal`` ranks strata by their sample variance; computing
(mean, M2) for tens of thousands of strata is a bandwidth-bound reduction.
This kernel tiles a (n_strata, n_samples) value matrix and combines block
moments with the Chan/Welford parallel-update rule while the block is still
in VMEM, so each value is read from HBM exactly once and the output is
O(n_strata) — the minimum possible traffic.

Grid: (row_blocks, col_blocks); the column axis revisits the accumulator
block via the shared :func:`repro.kernels.template.accumulate` pattern
(sequential semantics), identical to the mc_eval reduction — only the
``combine`` rule differs (Welford merge instead of add).  Pallas symbols
come from :mod:`repro.kernels.pallas_compat` so the kernel runs under any
supported jax (compiled on TPU, interpret mode elsewhere).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pallas_compat import pl, pltpu
from repro.kernels.template import accumulate

R_BLK = 8     # strata rows per grid step
C_BLK = 512   # samples per grid step (4 x 128 lanes)


def _welford_combine(acc, part):
    """Chan/Welford parallel update of stacked (n, mean, M2) rows."""
    n_a, mean_a, m2_a = acc[:, 0], acc[:, 1], acc[:, 2]
    n_b, mean_b, m2_b = part[:, 0], part[:, 1], part[:, 2]
    n = n_a + n_b
    delta = mean_b - mean_a
    mean = mean_a + delta * (n_b / n)
    m2 = m2_a + m2_b + jnp.square(delta) * (n_a * n_b / n)
    return jnp.stack([n, mean, m2], axis=1)


def _moments_kernel(vals_ref, out_ref):
    j = pl.program_id(1)
    v = vals_ref[...]                       # (R_BLK, C_BLK) f32
    n_b = jnp.float32(C_BLK)
    mean_b = jnp.mean(v, axis=1)            # (R_BLK,)
    m2_b = jnp.sum(jnp.square(v - mean_b[:, None]), axis=1)
    part = jnp.stack([jnp.full_like(mean_b, n_b), mean_b, m2_b], axis=1)
    accumulate(j, out_ref, part, combine=_welford_combine)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moments_pallas(values, *, interpret: bool):
    """(count, mean, M2) per row of ``values``.

    Args:
      values: f32[R, C] with R % R_BLK == 0 and C % C_BLK == 0 (ops.py pads).
    Returns:
      f32[R, 3].
    """
    r, c = values.shape
    assert r % R_BLK == 0 and c % C_BLK == 0, (r, c)
    grid = (r // R_BLK, c // C_BLK)
    return pl.pallas_call(
        _moments_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((R_BLK, C_BLK), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((R_BLK, 3), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 3), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="stratum_moments",
    )(values)
