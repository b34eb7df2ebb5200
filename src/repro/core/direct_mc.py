"""Direct Monte-Carlo engine (the v5/v5.1 sampling core).

The paper's ``ZMCintegral_functional`` / ``ZMCintegral_multifunctions``
classes both reduce to the same computation: for every integrand ``i`` draw
``N`` uniforms in its box and form

    mean_i   = vol_i / N * sum_s f_i(x_s)
    stderr_i = vol_i * sqrt( (E[f^2] - E[f]^2) / N )

This module provides that computation three ways:

* :func:`family_sums` — single-device, chunked over samples (and optionally
  over functions) so arbitrarily large (n_fn, N) fit in memory;
* :func:`family_sums` with ``kernel=...`` — the Pallas fused fast path for
  registered families (sampling + eval + block reduction in VMEM);
* :func:`sharded_family_sums` — the multi-chip path: functions shard over
  the ``model`` mesh axis, samples over ``data`` (and ``pod``); a single
  ``psum`` of the (s1, s2) partials over the sample axes finalises the
  estimate.  Communication is O(n_fn), independent of N — this is the
  compile-time form of the paper's "linear scaling with GPUs" claim.

Counters are global: sample ``s`` of function ``i`` uses the same Threefry
counter no matter how the work is split, so every path (single device,
sharded, kernel, restarted-from-checkpoint) computes *identical* sums up to
f32 association order.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import rng
from repro.core.domains import affine_from_unit, box_volume
from repro.core.integrand import IntegrandFamily


class SumsState(NamedTuple):
    """Raw accumulators; mergeable across chunks/devices/restarts."""
    s1: jax.Array      # (n_fn,) sum of f
    s2: jax.Array      # (n_fn,) sum of f^2
    n: jax.Array       # scalar or (n_fn,): samples accumulated


class MCResult(NamedTuple):
    mean: jax.Array    # (n_fn,) integral estimates
    stderr: jax.Array  # (n_fn,) standard error of the estimate
    n: jax.Array       # samples per function


def _eval_chunk(family: IntegrandFamily, k0, k1, fn_ids, sample_ids, valid,
                sampler: str = "mc"):
    """Evaluate one (n_fn, chunk) block of samples. Returns (s1, s2) sums."""
    if sampler == "sobol":
        from repro.core import sobol
        u = sobol.sobol_uniforms_for(k0, k1, fn_ids, sample_ids, family.dim)
    else:
        u = rng.uniforms_for(k0, k1, fn_ids, sample_ids, family.dim)
    x = affine_from_unit(u, family.domains[:, None, :, :])
    vals = family.eval_batch(x)
    vals = jnp.where(valid[None, :], vals, 0.0)
    return jnp.sum(vals, axis=-1), jnp.sum(jnp.square(vals), axis=-1)


def family_sums(
    family: IntegrandFamily,
    n_samples: int,
    key: tuple,
    *,
    fn_offset: int = 0,
    sample_offset: int = 0,
    chunk: int = 8192,
    fn_chunk: int | None = None,
    use_kernel: bool = False,
    sampler: str = "mc",
) -> SumsState:
    """Chunked (s1, s2) sums for every function in the family.

    Args:
      n_samples: samples per function contributed by *this* call.
      key: (k0, k1) uint32 Threefry key words.
      fn_offset: global id of this family's function 0 (multi-family specs).
      sample_offset: global index of the first sample (sharding / resume).
      chunk: samples per inner step; bounds peak memory at
        n_fn * chunk * dim floats.
      fn_chunk: optional function-axis blocking for >=10^4-integrand specs.
      use_kernel: dispatch to the registered Pallas fast path if the family
        declares one (``family.kernel``) *and* the registered form supports
        (dim, sampler); anything else falls back to the chunked path here.
        Whole-spec fusion (one launch per dim bucket) lives one level up,
        in ``ZMCMultiFunctions`` via ``repro.kernels.mc_eval.multi``.
    """
    n_fn = family.n_fn
    if fn_chunk is not None and fn_chunk < n_fn:
        return _fn_blocked_sums(family, n_samples, key, fn_offset=fn_offset,
                                sample_offset=sample_offset, chunk=chunk,
                                fn_chunk=fn_chunk)

    fn_ids = jnp.uint32(fn_offset) + jnp.arange(n_fn, dtype=jnp.uint32)
    return _sums_with_ids(family, n_samples, key, fn_ids,
                          jnp.uint32(sample_offset), chunk, use_kernel,
                          sampler=sampler)


def _fn_blocked_sums(family, n_samples, key, *, fn_offset, sample_offset,
                     chunk, fn_chunk) -> SumsState:
    """lax.map over function blocks to bound memory for huge n_fn."""
    n_fn = family.n_fn
    n_blocks = math.ceil(n_fn / fn_chunk)
    pad = n_blocks * fn_chunk - n_fn

    def pad_leaf(leaf):
        cfg = [(0, pad)] + [(0, 0)] * (leaf.ndim - 1)
        return jnp.pad(leaf, cfg)

    params = jax.tree.map(pad_leaf, family.params)
    domains = pad_leaf(family.domains)
    # padded rows get [0,1] boxes so volumes stay finite; results are sliced off
    if pad:
        domains = domains.at[n_fn:, :, 0].set(0.0).at[n_fn:, :, 1].set(1.0)

    def block(idx):
        sl = lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, idx * fn_chunk, fn_chunk)
        fam = IntegrandFamily(fn=family.fn, params=jax.tree.map(sl, params),
                              domains=sl(domains), name=family.name,
                              compact=family.compact)
        out = family_sums(fam, n_samples, key,
                          fn_offset=fn_offset + idx * fn_chunk,
                          sample_offset=sample_offset, chunk=chunk)
        return out.s1, out.s2

    s1b, s2b = jax.lax.map(block, jnp.arange(n_blocks))
    s1 = s1b.reshape(-1)[:n_fn]
    s2 = s2b.reshape(-1)[:n_fn]
    return SumsState(s1=s1, s2=s2, n=jnp.float32(n_samples))


def finalize(family: IntegrandFamily, sums: SumsState) -> MCResult:
    """Turn raw sums into (mean, stderr) integral estimates."""
    vol = box_volume(family.domains)
    n = jnp.maximum(sums.n, 1.0)
    mean_f = sums.s1 / n
    var_f = jnp.maximum(sums.s2 / n - jnp.square(mean_f), 0.0)
    return MCResult(mean=vol * mean_f,
                    stderr=vol * jnp.sqrt(var_f / n),
                    n=sums.n)


def merge_sums(a: SumsState, b: SumsState) -> SumsState:
    return SumsState(s1=a.s1 + b.s1, s2=a.s2 + b.s2, n=a.n + b.n)


# ---------------------------------------------------------------------------
# Sharded path
# ---------------------------------------------------------------------------

def _pad_family_to(family: IntegrandFamily, n_fn_padded: int) -> IntegrandFamily:
    pad = n_fn_padded - family.n_fn
    if pad == 0:
        return family

    def pad_leaf(leaf):
        cfg = [(0, pad)] + [(0, 0)] * (leaf.ndim - 1)
        return jnp.pad(leaf, cfg)

    domains = pad_leaf(family.domains)
    domains = domains.at[family.n_fn:, :, 0].set(0.0).at[family.n_fn:, :, 1].set(1.0)
    # padded compact rows get kind 0 (identity) from the zero-pad, so the
    # transform stage leaves them untouched
    return IntegrandFamily(fn=family.fn,
                           params=jax.tree.map(pad_leaf, family.params),
                           domains=domains, name=family.name,
                           kernel=family.kernel, compact=family.compact)


def sharded_family_sums(
    family: IntegrandFamily,
    n_samples: int,
    key: tuple,
    mesh: Mesh,
    *,
    fn_axis: str = "model",
    sample_axes: Sequence[str] = ("data",),
    fn_offset: int = 0,
    sample_offset: int = 0,
    chunk: int = 8192,
    use_kernel: bool = False,
    sampler: str = "mc",
):
    """Multi-chip (s1, s2) sums.

    Functions shard over ``fn_axis``; each sample-axis shard draws a disjoint
    counter range of samples; one psum over ``sample_axes`` merges partials.

    Returns ``(sums, padded_family)`` where arrays in ``sums`` have the
    padded n_fn length and carry a NamedSharding over ``fn_axis``.
    """
    sample_axes = tuple(sample_axes)
    fn_par = mesh.shape[fn_axis]
    sample_par = int(np.prod([mesh.shape[a] for a in sample_axes]))
    n_fn_padded = math.ceil(family.n_fn / fn_par) * fn_par
    fam = _pad_family_to(family, n_fn_padded)
    per_shard_samples = math.ceil(n_samples / sample_par)

    fn_ids = fn_offset + jnp.arange(n_fn_padded, dtype=jnp.uint32)
    k0, k1 = key

    fn_spec = P(fn_axis)
    rep = P()

    def local(params, domains, fn_ids_local):
        # which sample shard am I? -> disjoint global sample range
        idx = jnp.uint32(0)
        mult = 1
        for a in reversed(sample_axes):
            idx = idx + jnp.uint32(jax.lax.axis_index(a)) * jnp.uint32(mult)
            mult *= mesh.shape[a]
        shard_offset = (jnp.uint32(sample_offset)
                        + idx * jnp.uint32(per_shard_samples))
        fam_local = IntegrandFamily(fn=fam.fn, params=params, domains=domains,
                                    name=fam.name, kernel=fam.kernel,
                                    compact=fam.compact)
        # fn_offset already folded into fn_ids_local; pass offset via ids
        sums = _sums_with_ids(fam_local, per_shard_samples, (k0, k1),
                              fn_ids_local, shard_offset, chunk, use_kernel,
                              sampler=sampler)
        s1 = jax.lax.psum(sums.s1, sample_axes)
        s2 = jax.lax.psum(sums.s2, sample_axes)
        n = jnp.float32(per_shard_samples * sample_par)
        return s1, s2, n

    spec_params = jax.tree.map(lambda _: fn_spec, fam.params)
    out = shard_map(
        local, mesh=mesh,
        in_specs=(spec_params, fn_spec, fn_spec),
        out_specs=(fn_spec, fn_spec, rep),
        # the registered-kernel impl is a pallas_call
        check_vma=False,
    )(fam.params, fam.domains, fn_ids)
    s1, s2, n = out
    return SumsState(s1=s1, s2=s2, n=n), fam


def _sums_with_ids(family, n_samples, key, fn_ids, sample_offset, chunk,
                   use_kernel, sampler: str = "mc") -> SumsState:
    """Like family_sums but with explicit (traced) fn ids / sample offset.

    ``use_kernel`` dispatch is capability-checked: the registered Pallas
    fast path runs only if the family's form supports (dim, sampler) —
    compactified infinite-domain families included, gated by the form's
    ``supports_compactified`` flag; otherwise — unregistered form,
    unsupported dimension (e.g. Sobol beyond dim 8) — the chunked
    pure-JAX path below takes over silently.
    """
    if sampler == "sobol":
        from repro.core.sobol import MAX_DIM
        if family.dim > MAX_DIM:
            # documented sobol contract: beyond the Joe-Kuo table the
            # engine degrades to pseudo-random MC (still unbiased)
            sampler = "mc"
    if use_kernel and family.kernel is not None:
        from repro.kernels import registry
        impl = registry.lookup(family.kernel, dim=family.dim,
                               sampler=sampler,
                               compactified=family.compact,
                               sweep=family.swept,
                               adapted=bool(family.adapt_bins))
        if impl is not None:
            return impl(family, n_samples, key, fn_ids=fn_ids,
                        sample_offset=sample_offset)
    k0, k1 = key
    n_fn = family.n_fn
    n_chunks = max(1, math.ceil(n_samples / chunk))

    def body(i, acc):
        s1, s2 = acc
        start = jnp.uint32(sample_offset) + jnp.uint32(i) * jnp.uint32(chunk)
        sample_ids = start + jnp.arange(chunk, dtype=jnp.uint32)
        valid = (jnp.uint32(i) * jnp.uint32(chunk)
                 + jnp.arange(chunk, dtype=jnp.uint32)) < jnp.uint32(n_samples)
        c1, c2 = _eval_chunk(family, k0, k1, fn_ids, sample_ids, valid,
                             sampler=sampler)
        return (s1 + c1, s2 + c2)

    # derive the carry zeros from fn_ids AND sample_offset so that, under
    # shard_map, they carry the same varying-manual-axes type as the loop
    # body's outputs (fn_ids varies over the fn axis, sample_offset over the
    # sample axes)
    zeros = (0.0 * fn_ids.astype(jnp.float32)
             + 0.0 * jnp.asarray(sample_offset).astype(jnp.float32))
    s1, s2 = jax.lax.fori_loop(0, n_chunks, body, (zeros, zeros))
    return SumsState(s1=s1, s2=s2, n=jnp.float32(n_samples))
