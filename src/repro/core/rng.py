"""Counter-based random numbers for Monte-Carlo sampling.

ZMCintegral (the paper) uses Numba's per-thread stateful ``xoroshiro128+``
streams.  Stateful per-thread RNG does not survive the move to TPU SPMD:

* there is no per-thread scalar state inside a Pallas kernel,
* elastic restart / re-sharding would change which "thread" draws which
  sample, silently changing the estimate.

We therefore use a **counter-based** generator (Threefry-2x32, Salmon et al.
2011, the same family JAX's PRNG is built on): every scalar uniform is a pure
function ``u = T(key, counter)`` of a 64-bit key and a 64-bit counter.  The
counter encodes *which* sample this is — ``(function_id, dim, sample_index)``
— so the full sample stream is

* reproducible across restarts,
* independent of the mesh shape (elastic resharding draws identical numbers),
* computable *inside* a Pallas kernel with plain uint32 vector ops (no HBM
  traffic for random bits).

The identical algorithm is implemented three times and cross-checked by the
test-suite: here (pure jnp, the reference), in ``repro.kernels.mc_eval``
(Pallas), and implicitly via the oracle in ``repro.kernels.mc_eval.ref``.

Counter layout
--------------
``c0 = sample_index`` (uint32; up to 2**32 samples per function per key)
``c1 = function_id * DIM_STRIDE + dim_index`` (uint32)

``DIM_STRIDE = 256`` supports integrands of up to 256 dimensions and
``2**24 ≈ 1.6e7`` distinct functions per key — three orders of magnitude
beyond the paper's 10^4-integrand target.  Independent *trials* (the paper's
"10 independent evaluations") use distinct keys, derived by folding the trial
index into the key.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

# Up to 256 dims per integrand; function_id occupies the high 24 bits of c1.
DIM_STRIDE = 256

_KS_PARITY = np.uint32(0x1BD11BDA)
# Threefry-2x32 rotation schedule (two alternating groups of four rounds).
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))

_U32 = jnp.uint32
_INV_2_24 = np.float32(1.0 / (1 << 24))


def _rotl32(x, r: int):
    r = np.uint32(r)
    return (x << r) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Full 20-round Threefry-2x32 block cipher.

    All inputs are (broadcastable) uint32 arrays; returns the two uint32
    output words.  This is the standard Threefry-2x32 from Random123 —
    bit-exact with the version in ``repro.kernels.mc_eval.kernel`` (asserted
    by ``tests/kernels/test_rng_parity.py``).
    """
    k0 = jnp.asarray(k0, _U32)
    k1 = jnp.asarray(k1, _U32)
    x0 = jnp.asarray(c0, _U32) + k0
    x1 = jnp.asarray(c1, _U32) + k1
    ks = (k0, k1, k0 ^ k1 ^ _KS_PARITY)
    for group in range(5):
        rs = _ROTATIONS[group % 2]
        for r in rs:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(group + 1) % 3]
        x1 = x1 + ks[(group + 2) % 3] + np.uint32(group + 1)
    return x0, x1


def random_bits(k0, k1, c0, c1):
    """First output word of the Threefry block — one uint32 per counter."""
    return threefry2x32(k0, k1, c0, c1)[0]


def bits_to_uniform(bits):
    """Map uint32 bits to float32 uniforms in [0, 1).

    Uses the top 24 bits so the result is exactly representable in f32 and
    the mapping matches what the Pallas kernel computes with the same ops.
    The cast goes through int32, which is exact below 2^24: Mosaic has no
    uint32 -> float32 conversion.
    """
    top = (bits >> np.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * _INV_2_24


def fold_key(seed: int, stream: int = 0) -> tuple[np.uint32, np.uint32]:
    """Derive a (k0, k1) key pair from a python seed and a stream index.

    Distinct streams (e.g. independent trials) get statistically independent
    sample sets because the key enters every Threefry block.
    """
    seed = int(seed)
    k0 = np.uint32(seed & 0xFFFFFFFF)
    k1 = np.uint32(((seed >> 32) & 0xFFFFFFFF) ^ (int(stream) & 0xFFFFFFFF))
    # One mixing round so that (seed=0, stream=0) and (seed=0, stream=1)
    # do not share a trivially-related key.
    m0, m1 = threefry2x32(k0, k1, np.uint32(0x9E3779B9), np.uint32(0x7F4A7C15))
    return np.uint32(m0), np.uint32(m1)


def counter_c1(fn_ids, dims):
    """c1 word for (function_id, dim) pairs. Shapes broadcast."""
    fn_ids = jnp.asarray(fn_ids, _U32)
    dims = jnp.asarray(dims, _U32)
    return fn_ids * np.uint32(DIM_STRIDE) + dims


def uniforms_for(k0, k1, fn_ids, sample_ids, n_dim: int):
    """Uniform samples for a (function, sample, dim) grid.

    Args:
      k0, k1: uint32 key words.
      fn_ids: (F,) int array of global function ids.
      sample_ids: (S,) uint32 array of global sample indices.
      n_dim: number of dimensions to draw.

    Returns:
      (F, S, n_dim) float32 array of uniforms in [0, 1).
    """
    fn_ids = jnp.asarray(fn_ids)
    sample_ids = jnp.asarray(sample_ids, _U32)
    d = jnp.arange(n_dim, dtype=_U32)
    shape = (fn_ids.shape[0], sample_ids.shape[0], n_dim)
    c1 = jnp.broadcast_to(counter_c1(fn_ids[:, None, None], d[None, None, :]), shape)
    c0 = jnp.broadcast_to(sample_ids[None, :, None], shape)
    bits = random_bits(k0, k1, c0, c1)
    return bits_to_uniform(bits)
