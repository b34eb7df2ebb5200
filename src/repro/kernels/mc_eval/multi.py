"""Fused multi-family dispatch: one pallas_call per (dim, sampler) bucket.

The per-family loop in ``ZMCMultiFunctions._trial_sums`` launches one
kernel per family — fine for a handful of families, but the paper's
headline workload (>10^3 integrands, mixed forms and dimensions) wants
the original ZMCintegral property of splitting the *whole* batch across
the device in a single launch.  This module plans that:

1. every family whose ``kernel`` names a registered form that supports
   (dim, sampler) is **fusable** — compactified infinite-domain families
   included, via the transform wrapper stage and extra packed columns of
   ``template.body_and_packed``; the rest fall back to the chunked JAX
   path (the caller handles them);
2. fusable families are bucketed by integrand dimension (the kernel's
   sample-drawing loop is specialised on ``dim``);
3. within a bucket each family is padded to an F_BLK multiple (so every
   function block is homogeneous in form), packed parameters are padded
   to the bucket's widest form, and everything is concatenated into one
   operand set;
4. the whole bucket runs in a single ``pallas_call`` with per-block form
   ids driving ``lax.switch`` body selection (elided when the bucket has
   one distinct body);
5. results are sliced back out per family, in global-fn-id counter space
   — bit-identical to what the per-family launches would produce, since
   the Threefry/Sobol counters depend only on (global fn id, sample id).

The plan depends only on the spec (shapes, forms, dims) — callers build
it once and re-run it per trial/round with different keys/offsets.

Compile-cache keying: a bucket's kernel ``name`` (a static argument of
the jitted ``template.fused_mc_pallas``) encodes only the bucket's
**shape signature** — (sampler, dim, padded rows, packed cols) — never
which families produced it.  Two different request mixes that bucket to
the same shapes and the same body tuple therefore hit the same compiled
executable instead of retracing; only genuinely new shapes pay a
compile.

Multi-round plans: :func:`eval_plan_rounds` (and its mesh sibling
:func:`sharded_eval_plan_rounds`) evaluate R consecutive fixed-size
counter rounds of every bucket in ONE launch each — a refinement wave of
R rounds costs B launches instead of R x B.  Per-family ``start_rounds``
ride in a per-function-block SMEM operand, so streams parked at
different refinement depths still share the launch.  Each bucket's
``(R, rows, 2)`` output comes back whole, beside its slices, and no
device operation follows the launch: :func:`split_rounds` reads a stack
to the host with one copy and cuts it per family and round there.  The
per-round sums are bit-identical to the R single-round launches they
replace (the service cache's in-order fold and resume invariants depend
on this).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import registry, template
from repro.kernels.pallas_compat import resolve_interpret
from repro.kernels.template import F_BLK, S_BLK


@dataclasses.dataclass(frozen=True)
class _Slice:
    """Where one family's functions live inside a bucket's padded rows."""
    family_index: int
    row_start: int
    n_fn: int


@dataclasses.dataclass(frozen=True)
class _Bucket:
    """One fused launch: all same-dim fusable families, concatenated."""
    dim: int
    bodies: tuple            # distinct eval bodies, switch order
    packed: jnp.ndarray      # f32[n_fn_pad, n_cols_max]
    lo: jnp.ndarray          # f32[n_fn_pad, dim]
    hi: jnp.ndarray          # f32[n_fn_pad, dim]
    fn_ids: jnp.ndarray      # u32[n_fn_pad] global function ids
    form_ids: jnp.ndarray | None   # i32[n_fn_pad // F_BLK] or None
    slices: tuple[_Slice, ...]
    name: str


@dataclasses.dataclass(frozen=True)
class FusionPlan:
    buckets: tuple[_Bucket, ...]
    unfused: tuple[int, ...]   # family indices left to the chunked path
    sampler: str

    @property
    def n_launches(self) -> int:
        return len(self.buckets)


def plan_spec(spec, *, sampler: str = "mc",
              fn_offsets=None) -> FusionPlan:
    """Bucket a MultiFunctionSpec's fusable families by dimension.

    Args:
      spec: ``repro.core.integrand.MultiFunctionSpec``.
      sampler: "mc" | "sobol" — a family fuses only if its form supports
        this sampler at its dimension.
      fn_offsets: optional per-family global fn-id offsets (defaults to
        ``spec.offsets()``, the engine's counter layout).
    """
    families = spec.families
    if fn_offsets is None:
        fn_offsets = spec.offsets()

    by_dim: dict[int, list[int]] = {}
    unfused: list[int] = []
    for idx, fam in enumerate(families):
        form = registry.form(fam.kernel) if fam.kernel else None
        if form is None or not form.supports(
                dim=fam.dim, sampler=sampler, compactified=fam.compact,
                sweep=fam.swept, adapted=bool(fam.adapt_bins)):
            unfused.append(idx)
            continue
        by_dim.setdefault(fam.dim, []).append(idx)

    buckets = []
    for dim in sorted(by_dim):
        idxs = by_dim[dim]
        bodies: list = []
        packed_parts, lo_parts, hi_parts, id_parts = [], [], [], []
        block_forms: list[int] = []
        slices: list[_Slice] = []
        n_cols = max(template.packed_cols(registry.form(families[i].kernel),
                                          families[i]) for i in idxs)
        row = 0
        for idx in idxs:
            fam = families[idx]
            form = registry.form(fam.kernel)
            body, packed = template.body_and_packed(form, fam)
            if body not in bodies:
                bodies.append(body)
            body_ix = bodies.index(body)

            n_fn = fam.n_fn
            n_fn_pad = math.ceil(n_fn / F_BLK) * F_BLK
            pad = n_fn_pad - n_fn
            packed = template.pad_rows(packed, pad)
            if packed.shape[1] < n_cols:
                packed = jnp.pad(
                    packed, ((0, 0), (0, n_cols - packed.shape[1])))
            packed_parts.append(packed)
            lo_parts.append(template.pad_rows(
                jnp.asarray(fam.domains[..., 0], jnp.float32), pad))
            hi_parts.append(template.pad_rows(
                jnp.asarray(fam.domains[..., 1], jnp.float32), pad))
            id_parts.append(template.pad_rows(
                jnp.uint32(fn_offsets[idx])
                + jnp.arange(n_fn, dtype=jnp.uint32), pad))
            block_forms += [body_ix] * (n_fn_pad // F_BLK)
            slices.append(_Slice(idx, row, n_fn))
            row += n_fn_pad

        form_ids = (jnp.asarray(np.asarray(block_forms, np.int32))
                    if len(bodies) > 1 else None)
        buckets.append(_Bucket(
            dim=dim,
            bodies=tuple(bodies),
            packed=jnp.concatenate(packed_parts),
            lo=jnp.concatenate(lo_parts),
            hi=jnp.concatenate(hi_parts),
            fn_ids=jnp.concatenate(id_parts),
            form_ids=form_ids,
            slices=tuple(slices),
            # shape-signature name: identical for every entry mix that
            # buckets to these shapes, so the jit compile cache is keyed
            # by what the compiler actually sees, not by which families
            # happened to arrive (see module docstring)
            name=f"mc_eval_fused_{sampler}_d{dim}f{row}c{n_cols}",
        ))
    return FusionPlan(buckets=tuple(buckets), unfused=tuple(unfused),
                      sampler=sampler)


def eval_plan(plan: FusionPlan, n_samples: int, key, *,
              sample_offset=0, interpret: bool | None = None):
    """Run every bucket of a plan; returns {family_index: SumsState}.

    Same counter space as the per-family path: family ``i``'s sums are
    identical (up to f32 association order) to
    ``family_sums(families[i], ..., use_kernel=True)``.
    """
    from repro.core.direct_mc import SumsState

    interpret = resolve_interpret(interpret)
    n_sample_blocks = max(1, math.ceil(int(n_samples) / S_BLK))
    scalars = template.pack_scalars(key, sample_offset, n_samples)

    out: dict[int, SumsState] = {}
    for bucket in plan.buckets:
        dirvecs = None
        if plan.sampler == "sobol":
            from repro.core.sobol import direction_vectors
            dirvecs = jnp.asarray(direction_vectors(bucket.dim))
        template.record_launch()
        sums = template.fused_mc_pallas(
            scalars, bucket.fn_ids, bucket.packed, bucket.lo, bucket.hi,
            form_ids=bucket.form_ids, dirvecs=dirvecs, dim=bucket.dim,
            n_sample_blocks=n_sample_blocks, bodies=bucket.bodies,
            sampler=plan.sampler, interpret=interpret, name=bucket.name)[0]
        for sl in bucket.slices:
            rows = sums[sl.row_start:sl.row_start + sl.n_fn]
            out[sl.family_index] = SumsState(
                s1=rows[:, 0], s2=rows[:, 1], n=jnp.float32(n_samples))
    return out


def _round_base_for(bucket: _Bucket, start_rounds, round_samples: int):
    """u32 per-function-block window starts for a multi-round launch.

    ``start_rounds`` maps family_index -> absolute index of the first
    round this launch evaluates for that family.  Blocks are per-family
    by construction (families are padded to F_BLK multiples), so the
    per-block value is exact; shard-padding blocks keep offset 0 (their
    rows are sliced off anyway).
    """
    n_blocks = bucket.fn_ids.shape[0] // F_BLK
    base = np.zeros(n_blocks, np.uint32)
    for sl in bucket.slices:
        b0 = sl.row_start // F_BLK
        nb = math.ceil(sl.n_fn / F_BLK)
        # counters are u32: streams wrap at 2^32 samples, exactly like
        # the scalar sample_offset path
        start = (int(start_rounds[sl.family_index]) * int(round_samples))
        base[b0:b0 + nb] = np.uint32(start & 0xFFFFFFFF)
    return jnp.asarray(base)


def _untimed(name: str, *labels):
    return _UNTIMED


_UNTIMED = contextlib.nullcontext()


def eval_plan_rounds(plan: FusionPlan, round_samples: int, n_rounds: int,
                     key, *, start_rounds, interpret: bool | None = None,
                     part=None):
    """R consecutive fixed-size rounds of every bucket, ONE launch each.

    Args:
      round_samples: samples per round (every round is full-size; the
        service cache's round quantum).
      n_rounds: consecutive rounds to evaluate per family.
      start_rounds: family_index -> absolute first round index; families
        may start at different depths (fused top-ups).
      part: ``part(name, label)`` -> context manager timing one step:
        ``dispatch`` of the shared scalars (label ``scalars``), then per
        bucket its ``dispatch`` (operands and ``fused_mc_pallas``),
        labelled with the kernel's name; a trace span's ``part``
        (:mod:`repro.obs.trace`).  None times nothing.
    Returns:
      ``((sums, slices), ...)``, one pair per bucket: ``sums`` is the
      launch's own f32 ``(n_rounds, rows, 2)`` output, still a device
      future (nothing is dispatched after the kernel), and ``slices``
      says where each family's rows lie.  :func:`split_rounds` turns it
      into per-family rounds, each bit-identical to the single-round
      :func:`eval_plan` call at ``sample_offset = round * round_samples``.
    """
    interpret = resolve_interpret(interpret)
    n_sample_blocks = max(1, math.ceil(int(round_samples) / S_BLK))
    part = part or _untimed
    with part("dispatch", "scalars"):
        scalars = template.pack_scalars(key, 0, round_samples,
                                        round_stride=round_samples)

    out = []
    for bucket in plan.buckets:
        name = f"{bucket.name}_r{n_rounds}"
        with part("dispatch", name):
            dirvecs = None
            if plan.sampler == "sobol":
                from repro.core.sobol import direction_vectors
                dirvecs = jnp.asarray(direction_vectors(bucket.dim))
            round_base = _round_base_for(bucket, start_rounds,
                                         round_samples)
            template.record_launch()
            sums = template.fused_mc_pallas(
                scalars, bucket.fn_ids, bucket.packed, bucket.lo, bucket.hi,
                form_ids=bucket.form_ids, round_base=round_base,
                dirvecs=dirvecs, dim=bucket.dim,
                n_sample_blocks=n_sample_blocks, bodies=bucket.bodies,
                n_rounds=n_rounds, sampler=plan.sampler,
                interpret=interpret, name=name)
        out.append((sums, bucket.slices))
    return tuple(out)


def split_rounds(stacks, round_samples: int):
    """Per-family rounds of :func:`eval_plan_rounds`'s (or
    :func:`sharded_eval_plan_rounds`'s) output, cut on the host.

    Each bucket's stack is read with ONE device-to-host copy (none if it
    is a host array already); every round's ``s1``/``s2`` is a numpy view
    of it and ``n`` a host ``np.float32``.  Returns
    ``{family_index: (SumsState, ...)}``, ``n_rounds`` states in round
    order.
    """
    from repro.core.direct_mc import SumsState

    n = np.float32(round_samples)
    out: dict[int, tuple] = {}
    for sums, slices in stacks:
        host = np.asarray(sums)
        for sl in slices:
            rows = host[:, sl.row_start:sl.row_start + sl.n_fn]
            out[sl.family_index] = tuple(
                SumsState(s1=rows[r, :, 0], s2=rows[r, :, 1], n=n)
                for r in range(rows.shape[0]))
    return out


def _shard_bucket(bucket: _Bucket, fn_par: int) -> _Bucket:
    """Pad a bucket so its F_BLK blocks divide evenly over ``fn_par``.

    Padded rows are zeros (sliced off by the caller, exactly like the
    per-family padding) and padded blocks carry body index 0.
    """
    blocks = bucket.fn_ids.shape[0] // F_BLK
    tgt_blocks = math.ceil(blocks / fn_par) * fn_par
    extra = (tgt_blocks - blocks) * F_BLK
    if extra == 0:
        return bucket
    form_ids = bucket.form_ids
    if form_ids is not None:
        form_ids = jnp.concatenate(
            [form_ids, jnp.zeros(tgt_blocks - blocks, jnp.int32)])
    return dataclasses.replace(
        bucket,
        packed=template.pad_rows(bucket.packed, extra),
        lo=template.pad_rows(bucket.lo, extra),
        hi=template.pad_rows(bucket.hi, extra),
        fn_ids=template.pad_rows(bucket.fn_ids, extra),
        form_ids=form_ids,
    )


def sharded_eval_plan(plan: FusionPlan, n_samples: int, key, mesh, *,
                      fn_axis: str = "model", sample_axes=("data",),
                      sample_offset=0, interpret: bool | None = None):
    """Mesh variant of :func:`eval_plan`: one fused launch per bucket,
    *inside* ``shard_map``.

    The bucketed operands are built once on the host (same planner as the
    single-device path), then function rows shard over ``fn_axis`` and
    each sample-axis shard draws a disjoint counter range; a single
    ``psum`` over the sample axes merges the (s1, s2) partials — the same
    communication shape as ``direct_mc.sharded_family_sums``, but one
    launch per (dim, sampler) bucket instead of one per family.

    Returns {family_index: SumsState} with ``n`` *exactly* ``n_samples``:
    unlike the per-family sharded path, the last shard masks its tail
    instead of rounding the total up, so counter ranges of consecutive
    windows (``sample_offset`` advancing by ``n_samples``) never overlap
    — the invariant the service cache's top-up fold relies on.
    """
    from repro.compat import shard_map
    from repro.core.direct_mc import SumsState

    interpret = resolve_interpret(interpret)
    sample_axes = tuple(sample_axes)
    fn_par = mesh.shape[fn_axis]
    sample_par = int(np.prod([mesh.shape[a] for a in sample_axes]))
    per_shard = math.ceil(int(n_samples) / sample_par)
    n_sample_blocks = max(1, math.ceil(per_shard / S_BLK))
    k0, k1 = key
    fs = P(fn_axis)

    out: dict[int, SumsState] = {}
    for bucket in plan.buckets:
        sb = _shard_bucket(bucket, fn_par)
        dirvecs = None
        if plan.sampler == "sobol":
            from repro.core.sobol import direction_vectors
            dirvecs = jnp.asarray(direction_vectors(sb.dim))

        def local(fn_ids, packed, lo, hi, form_ids, *, _bucket=sb,
                  _dirvecs=dirvecs):
            idx = jnp.uint32(0)
            mult = 1
            for a in reversed(sample_axes):
                idx = idx + jnp.uint32(jax.lax.axis_index(a)) * jnp.uint32(mult)
                mult *= mesh.shape[a]
            # exact split: the last shard masks the tail so the call draws
            # precisely n_samples counters in total
            start = jnp.minimum(idx * jnp.uint32(per_shard),
                                jnp.uint32(n_samples))
            n_local = jnp.minimum(jnp.uint32(n_samples) - start,
                                  jnp.uint32(per_shard))
            shard_offset = jnp.uint32(sample_offset) + start
            scalars = template.pack_scalars((k0, k1), shard_offset, n_local)
            sums = template.fused_mc_pallas(
                scalars, fn_ids, packed, lo, hi, form_ids=form_ids,
                dirvecs=_dirvecs, dim=_bucket.dim,
                n_sample_blocks=n_sample_blocks, bodies=_bucket.bodies,
                sampler=plan.sampler, interpret=interpret,
                name=_bucket.name + "_sharded")[0]
            return jax.lax.psum(sums, sample_axes)

        in_specs = [fs, fs, fs, fs]
        args = [sb.fn_ids, sb.packed, sb.lo, sb.hi]
        if sb.form_ids is not None:
            in_specs.append(fs)
            args.append(sb.form_ids)
        else:
            local = functools.partial(local, form_ids=None)
        template.record_launch()
        sums = shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=fs, check_vma=False)(*args)
        n_actual = jnp.float32(int(n_samples))
        for sl in bucket.slices:
            rows = sums[sl.row_start:sl.row_start + sl.n_fn]
            out[sl.family_index] = SumsState(
                s1=rows[:, 0], s2=rows[:, 1], n=n_actual)
    return out


def sharded_eval_plan_rounds(plan: FusionPlan, round_samples: int,
                             n_rounds: int, key, mesh, *, start_rounds,
                             fn_axis: str = "model", sample_axes=("data",),
                             interpret: bool | None = None, part=None):
    """Mesh variant of :func:`eval_plan_rounds`: R rounds x B buckets in
    B launches, *inside* ``shard_map``; ``part`` and the return as there
    (a stack keeps the shard padding's rows past its last slice).

    Each sample-axis shard evaluates its window of every round (the last
    shard masks the tail, so each round draws exactly ``round_samples``
    counters globally); one ``psum`` over the sample axes merges the
    whole (n_rounds, fn, 2) stack at once.  Per-round sums are
    bit-identical to ``n_rounds`` separate :func:`sharded_eval_plan`
    calls: same per-shard counters, same in-shard fold order, and the
    psum applies the same per-element association order regardless of
    how many rounds ride in the stack.
    """
    from repro.compat import shard_map

    interpret = resolve_interpret(interpret)
    sample_axes = tuple(sample_axes)
    fn_par = mesh.shape[fn_axis]
    sample_par = int(np.prod([mesh.shape[a] for a in sample_axes]))
    per_shard = math.ceil(int(round_samples) / sample_par)
    n_sample_blocks = max(1, math.ceil(per_shard / S_BLK))
    k0, k1 = key
    fs = P(fn_axis)

    part = part or _untimed
    out = []
    for bucket in plan.buckets:
        name = f"{bucket.name}_r{n_rounds}_sharded"
        with part("dispatch", name):
            sb = _shard_bucket(bucket, fn_par)
            round_base = _round_base_for(sb, start_rounds, round_samples)
            dirvecs = None
            if plan.sampler == "sobol":
                from repro.core.sobol import direction_vectors
                dirvecs = jnp.asarray(direction_vectors(sb.dim))

            def local(fn_ids, packed, lo, hi, round_base, form_ids, *,
                      _bucket=sb, _dirvecs=dirvecs):
                idx = jnp.uint32(0)
                mult = 1
                for a in reversed(sample_axes):
                    idx = idx + (jnp.uint32(jax.lax.axis_index(a))
                                 * jnp.uint32(mult))
                    mult *= mesh.shape[a]
                start = jnp.minimum(idx * jnp.uint32(per_shard),
                                    jnp.uint32(round_samples))
                n_local = jnp.minimum(jnp.uint32(round_samples) - start,
                                      jnp.uint32(per_shard))
                scalars = template.pack_scalars((k0, k1), start, n_local,
                                                round_stride=round_samples)
                sums = template.fused_mc_pallas(
                    scalars, fn_ids, packed, lo, hi, form_ids=form_ids,
                    round_base=round_base, dirvecs=_dirvecs,
                    dim=_bucket.dim,
                    n_sample_blocks=n_sample_blocks, bodies=_bucket.bodies,
                    n_rounds=n_rounds, sampler=plan.sampler,
                    interpret=interpret,
                    name=f"{_bucket.name}_r{n_rounds}_sharded")
                return jax.lax.psum(sums, sample_axes)

            in_specs = [fs, fs, fs, fs, fs]
            args = [sb.fn_ids, sb.packed, sb.lo, sb.hi, round_base]
            if sb.form_ids is not None:
                in_specs.append(fs)
                args.append(sb.form_ids)
            else:
                local = functools.partial(local, form_ids=None)
            template.record_launch()
            sums = shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=P(None, fn_axis),
                             check_vma=False)(*args)
        out.append((sums, bucket.slices))
    return tuple(out)
