"""A plain Monte Carlo estimator in ``jax.numpy``: uniform draws, the
integrand, per-tile sums, nothing of the system under test.

It computes in the dtype it is given: draws are rounded to it, the
integrand and each tile's sums are computed in it, and tile sums are
folded in float32 per chunk and in float64 on the host.  In float32 it is
a reference; in bfloat16 it is the benchmark's control, the step below the
float32 the configurations state.
"""

from __future__ import annotations

import math

import numpy as np

TILE = 2048          # samples per tile sum, as one grid step of the kernel
CHUNK = 65536        # samples per function per chunk
FN_BLOCK = 16        # functions per jitted call


def plain_mc(integrand, params: dict, dim: int, n: int, dtype, seed: int):
    """Mean and stderr (float64) of every function's integral over the unit
    cube from ``n`` samples (rounded up to whole chunks).  Returns
    ``(means, stderrs, n_used)``."""
    import jax
    import jax.numpy as jnp

    n_chunks = max(1, math.ceil(int(n) / CHUNK))
    n_used = n_chunks * CHUNK
    n_fn = len(next(iter(params.values())))
    pad = (-n_fn) % FN_BLOCK

    def padded(x):
        x = np.asarray(x)
        return np.concatenate([x, np.repeat(x[:1], pad, axis=0)]) if pad else x

    host = {k: padded(v) for k, v in params.items()}

    @jax.jit
    def block(p, key):
        p = {k: v.astype(dtype) for k, v in p.items()}

        def step(_, i):
            u = jax.random.uniform(jax.random.fold_in(key, i),
                                   (FN_BLOCK, CHUNK, dim), jnp.float32)
            f = integrand(u.astype(dtype), p).reshape(FN_BLOCK, -1, TILE)
            s1 = jnp.sum(f, axis=-1, dtype=dtype).astype(jnp.float32)
            s2 = jnp.sum(f * f, axis=-1, dtype=dtype).astype(jnp.float32)
            return None, (s1.sum(-1), s2.sum(-1))

        return jax.lax.scan(step, None, jnp.arange(n_chunks))[1]

    key = jax.random.key(seed % (1 << 63))
    s1 = np.zeros(n_fn + pad)
    s2 = np.zeros(n_fn + pad)
    for b in range(0, n_fn + pad, FN_BLOCK):
        blk = {k: jnp.asarray(v[b:b + FN_BLOCK]) for k, v in host.items()}
        c1, c2 = block(blk, jax.random.fold_in(key, b))
        s1[b:b + FN_BLOCK] = np.asarray(c1, np.float64).sum(axis=0)
        s2[b:b + FN_BLOCK] = np.asarray(c2, np.float64).sum(axis=0)
    mean = s1[:n_fn] / n_used
    var = np.maximum(s2[:n_fn] / n_used - mean * mean, 0.0)
    return mean, np.sqrt(var / n_used), n_used
