"""Shared scaffolding for fused MC sample+eval+reduce Pallas kernels.

Every fused MC kernel in this repo has the same shape: a
``(n_fn_blocks, n_sample_blocks)`` grid, per-function parameters blocked
``F_BLK`` rows at a time, uniforms generated *inside* VMEM (counter-based
Threefry or digitally-shifted Sobol — random bits never touch HBM), an
integrand evaluated on (S_ROWS, S_LANES) vector tiles, and per-function
(sum f, sum f^2) partials accumulated in place across the sample-block
grid axis (the output BlockSpec maps every ``j`` to the same block — the
canonical TPU reduction pattern).

This module owns that scaffolding once.  A registered integrand form
(:class:`repro.kernels.registry.KernelForm`) supplies only

* an **eval body** ``body(draw, p, f, dim) -> (S_ROWS, S_LANES) tile``,
  where ``draw(d)`` yields the domain-mapped sample tile for dimension
  ``d`` of function ``f`` and ``p`` is the (F_BLK, n_cols) packed
  parameter block, and
* a **param packer** ``pack_params(family) -> f32[n_fn, n_cols]``,

and gets single-family *and* fused multi-family kernels for both samplers
for free (:func:`make_family_impl`, :mod:`repro.kernels.mc_eval.multi`).

Multi-form dispatch: when one launch covers families with different eval
bodies, each F_BLK function block is homogeneous in form (families are
padded to F_BLK multiples before concatenation) and carries a per-block
form id in SMEM; the kernel selects the body with ``jax.lax.switch`` once
per block.  Sampling, domain mapping and reduction are shared across
forms — this is what lets a heterogeneous ``MultiFunctionSpec`` run in
one ``pallas_call`` per (dim, sampler) bucket instead of one per family.

Infinite domains: a compactified family (``IntegrandFamily.compact``)
evaluates through the same machinery with a **wrapper stage** around its
form's body (:func:`compactified_body`): the per-axis transform kind and
shift ride as extra packed parameter columns, the wrapper maps every
draw through the tangent/rational compactification shared with the
chunked path (``repro.core.domains.apply_transform``) and folds the
Jacobian product into the value tile.  The wrapped body participates in
``lax.switch`` selection like any other, so finite and infinite-domain
families fuse into the same (dim, sampler) bucket launches.

Parameter sweeps: a swept family (``IntegrandFamily.swept``, built by
``swept_over``) runs a single-function template over a grid of parameter
points through a second **wrapper stage** (:func:`swept_body`), mirroring
the compactified one: the per-point table values ride as extra packed
columns after the form's base columns, and the wrapper substitutes them
into the template's packed row (static column indexing — no gather)
before the form's body reads it.  Every grid point is an ordinary
function row with its own global fn id and counter stream, so a whole
sweep chunk runs in ONE ``pallas_call`` per (dim, sampler) bucket while
staying bit-identical to evaluating each point as its own family.  The
stages compose — a compactified sweep packs
``[base cols][sweep cols][transform cols]`` and wraps
``compactified_body(swept_body(body))``.

Adaptive importance sampling: an adapted family
(``IntegrandFamily.adapt_bins``, built by ``IntegrandFamily.adapted``
from a VEGAS grid fit — :mod:`repro.core.adaptive`) samples the unit
cube and maps each draw through its per-axis inverse-CDF grid via a
third wrapper stage (:func:`adapted_body`): the ``dim * (n_bins + 1)``
bin-edge columns ride after the form's base (and sweep) columns, the
wrapper bin-selects with static unrolled column reads (no gather) and
folds the bin-width Jacobian product into the value tile.  The full
composition for an adapted compactified family is
``adapted_body(compactified_body(body))`` over a
``[base][sweep][adapt][transform]`` column layout — draws are uniforms,
the adapt stage maps them into the compactified box, the transform
stage maps onward to the original (possibly infinite) coordinates.
Adapted streams therefore fuse into the same (dim, sampler) bucket
launches as everything else, and their counters depend only on (global
fn id, sample id) exactly like an unadapted stream's.

Multi-round evaluation: the grid carries an optional **round axis**
(``n_rounds``) so one launch evaluates R consecutive counter-addressed
sample windows, emitting per-round ``(sum f, sum f^2)`` partials in an
``f32[n_rounds, n_fn_pad, 2]`` output.  Round ``r`` draws the counters
``base + r * round_stride + [0, n_valid)`` — exactly the counters a
separate launch with ``sample_offset = base + r * round_stride`` would
draw, and each round's accumulator folds its sample blocks in the same
order — so per-round sums are **bit-identical** to R single-round
launches.  An optional per-function-block ``round_base`` operand lets
function blocks start their windows at different offsets (the service
fuses cache streams sitting at different refinement depths into one
launch); blocks are per-family, so the Sobol point construction stays
shared per (tile, dim) exactly as in the single-round kernel.

All Pallas symbols come from :mod:`repro.kernels.pallas_compat` (the
version-drift shim); nothing here imports ``jax.experimental`` directly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import domains as domains_lib
from repro.core import rng as rng_lib
from repro.kernels.pallas_compat import pl, pltpu, resolve_interpret

# Sample tile: 16 sublanes x 128 lanes = 2048 samples per grid step.
S_ROWS = 16
S_LANES = 128
S_BLK = S_ROWS * S_LANES
# Functions per grid step.
F_BLK = 16

# c0 plane reserved for per-(function, dim) Sobol digital shifts; must
# match the pure-jnp oracle in repro.core.sobol.
SOBOL_SHIFT_C0 = 0x50B01

# Python-level pallas_call launch counter (incremented by the ops-layer
# wrappers each dispatch; launches made while tracing inside an outer jit
# count once at trace time).  benchmarks/kernel_bench.py uses this to show
# the fused path needs fewer launches than the per-family loop.
_LAUNCHES = 0


def record_launch() -> None:
    global _LAUNCHES
    _LAUNCHES += 1


def launch_count() -> int:
    return _LAUNCHES


def reset_launch_count() -> None:
    global _LAUNCHES
    _LAUNCHES = 0


def pad_rows(x, n_pad: int):
    """Zero-pad the leading (function) axis by ``n_pad`` rows."""
    if n_pad == 0:
        return x
    return jnp.pad(x, [(0, n_pad)] + [(0, 0)] * (x.ndim - 1))


def tile_sample_index(j):
    """Call-local sample index of each lane of the (S_ROWS, S_LANES) tile
    for sample-block ``j``."""
    row = jax.lax.broadcasted_iota(jnp.uint32, (S_ROWS, S_LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (S_ROWS, S_LANES), 1)
    local = row * jnp.uint32(S_LANES) + col
    return jnp.uint32(j) * jnp.uint32(S_BLK) + local


def accumulate(j, out_ref, part, combine=None):
    """In-place accumulator across the sequential grid axis ``j``.

    First visit stores ``part``; later visits fold it in with ``combine``
    (default: elementwise add).  The caller's output BlockSpec must map
    every ``j`` to the same block.
    """

    @pl.when(j == 0)
    def _init():
        out_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        if combine is None:
            out_ref[...] = out_ref[...] + part
        else:
            out_ref[...] = combine(out_ref[...], part)


def sobol_tiles(idx, v, dim: int):
    """Unshifted Sobol points for one index tile: list of dim u32 tiles.

    Gray-code-by-index construction: point ``i`` is the XOR of the
    direction vectors selected by the bits of ``gray(i)`` — O(32) vector
    ops, shared by every function in the block.
    """
    gray = idx ^ (idx >> jnp.uint32(1))
    outs = [jnp.zeros(idx.shape, jnp.uint32) for _ in range(dim)]
    for j in range(32):
        bit = ((gray >> jnp.uint32(j)) & jnp.uint32(1)).astype(bool)
        for d in range(dim):
            outs[d] = outs[d] ^ jnp.where(bit, v[d, j], jnp.uint32(0))
    return outs


@functools.lru_cache(maxsize=None)
def compactified_body(body, base_cols: int):
    """Wrap an eval body with the infinite-domain compactification stage.

    A compactified family's packed parameters carry, after its form's
    ``base_cols`` columns, ``2 * dim`` transform columns:
    ``[kind_0..kind_{dim-1}, shift_0..shift_{dim-1}]`` (kind codes are
    ``repro.core.domains.TRANSFORM_*`` — exact small ints in f32).  The
    wrapper draws every dimension once, maps each tile through the
    tangent/rational transform shared with the chunked path
    (``domains.apply_transform``), hands the body pre-transformed draws,
    and folds the per-axis Jacobian product into the returned value tile.

    lru_cached so every plan of the same (body, base_cols) pair reuses
    ONE wrapper object: bucket body dedupe and the jit compile cache both
    key on body identity.
    """

    def wrapped(draw, p, f, dim: int):
        xs = []
        jac = None
        for d in range(dim):
            x, j = domains_lib.apply_transform(
                draw(d), p[f, base_cols + d], p[f, base_cols + dim + d])
            xs.append(x)
            jac = j if jac is None else jac * j
        val = body(lambda d: xs[d], p, f, dim)
        return val * jac

    wrapped.__name__ = f"compactified_{getattr(body, '__name__', 'body')}"
    return wrapped


def transform_cols(family):
    """f32[n_fn, 2 * dim] packed (kind, shift) columns of a compactified
    family, appended after its form's own parameter columns."""
    aux = family.params["aux"]
    return jnp.concatenate([
        jnp.asarray(aux["kind"], jnp.float32),
        jnp.asarray(aux["shift"], jnp.float32)], axis=1)


@functools.lru_cache(maxsize=None)
def swept_body(body, base_cols: int, col_map: tuple):
    """Wrap an eval body with the parameter-sweep substitution stage.

    A swept family's packed parameters carry, after its form's
    ``base_cols`` columns, one table column per swept parameter column;
    ``col_map[j]`` names the base column that table column ``j``
    overrides (:func:`sweep_col_map` derives it from
    ``KernelForm.sweep_cols``).  The wrapper redirects the body's
    parameter reads through a column-substitution view: ``p[f, c]``
    resolves to the table column when ``c`` is overridden and to the
    base column otherwise.  Substitution happens at the *read site*
    (static Python index arithmetic, no gather, no rebuilt block), so
    the traced kernel issues exactly the per-point program's scalar
    reads at shifted column constants — XLA sees a structurally
    identical computation and bit-identity to the per-point path is
    preserved through fusion/contraction choices, not just in exact
    arithmetic.  Counters depend only on (global fn id, sample id), so
    the values agree too.

    lru_cached for the same reason as :func:`compactified_body`: bucket
    body dedupe and the jit compile cache key on body identity.
    """
    subst = {col_map[j]: base_cols + j for j in range(len(col_map))}

    class _SubstView:
        """Redirects ``[f, c]`` parameter reads through the sweep map."""
        __slots__ = ("p",)

        def __init__(self, p):
            self.p = p

        def __getitem__(self, idx):
            f, c = idx
            return self.p[f, subst.get(c, c)]

    def wrapped(draw, p, f, dim: int):
        return body(draw, _SubstView(p), f, dim)

    wrapped.__name__ = f"swept_{getattr(body, '__name__', 'body')}"
    return wrapped


def sweep_col_map(form, family) -> tuple:
    """Base-column substitution map of a swept ``family`` under ``form``.

    Entry ``j`` is the base packed column that sweep table column ``j``
    overrides; table columns are laid out name-major in ``family.swept``
    order (sorted names), each name contributing its
    ``form.sweep_cols(dim)`` columns in declared order.  Takes the
    non-compact (:meth:`IntegrandFamily.inner`) swept view.  Raises if
    the form doesn't advertise the swept names or a table leaf's width
    disagrees with the form's column map.
    """
    if form.sweep_cols is None:
        raise ValueError(
            f"kernel form {form.name!r} does not support swept families")
    cols = form.sweep_cols(family.dim)
    table = family.params["table"]
    out = []
    for name in family.swept:
        if name not in cols:
            raise ValueError(
                f"kernel form {form.name!r} cannot sweep parameter "
                f"{name!r} at dim={family.dim}; sweepable: {sorted(cols)}")
        width = 1
        for s in jnp.shape(table[name])[1:]:
            width *= int(s)
        if width != len(cols[name]):
            raise ValueError(
                f"sweep axis {name!r} packs {width} column(s) per point "
                f"but form {form.name!r} maps it to {len(cols[name])} "
                f"base column(s) at dim={family.dim}")
        out.extend(int(c) for c in cols[name])
    return tuple(out)


def sweep_table_cols(family):
    """f32[n_fn, n_sweep_cols] packed per-point table columns of a swept
    family (non-compact view), appended after its form's base columns in
    :func:`sweep_col_map` order."""
    table = family.params["table"]
    return jnp.concatenate(
        [jnp.asarray(table[name], jnp.float32).reshape(family.n_fn, -1)
         for name in family.swept], axis=1)


@functools.lru_cache(maxsize=None)
def adapted_body(body, base_cols: int, n_bins: int):
    """Wrap an eval body with the VEGAS importance-map stage.

    An adapted family's packed parameters carry, after its form's (and
    sweep's) ``base_cols`` columns, ``dim * (n_bins + 1)`` bin-edge
    columns — axis-major, so axis ``d``'s edges sit at
    ``base_cols + d * (n_bins + 1)``.  The family's domain box is the
    unit cube, so ``draw(d)`` yields a raw uniform tile; the wrapper
    bin-selects with a static unrolled loop (scalar column reads +
    ``jnp.where`` — no gather, which Mosaic would reject), linearly
    interpolates inside the selected bin, hands the body the mapped
    draws, and folds the per-axis ``n_bins * bin_width`` Jacobian
    product into the returned value tile.  The arithmetic mirrors
    :func:`repro.core.adaptive.apply_map` expression for expression, so
    the fused and chunked paths agree on adapted streams exactly like
    they do on compactified ones.

    lru_cached for the same reason as :func:`compactified_body`: bucket
    body dedupe and the jit compile cache key on body identity.
    """

    def wrapped(draw, p, f, dim: int):
        xs = []
        jac = None
        for d in range(dim):
            u = draw(d)
            s = u * float(n_bins)
            idx = jnp.minimum(s.astype(jnp.int32), n_bins - 1)
            frac = s - idx.astype(jnp.float32)
            col = base_cols + d * (n_bins + 1)
            x = jnp.zeros_like(u)
            w = jnp.zeros_like(u)
            for b in range(n_bins):
                e0 = p[f, col + b]
                e1 = p[f, col + b + 1]
                sel = idx == b
                x = jnp.where(sel, e0 + frac * (e1 - e0), x)
                w = jnp.where(sel, (e1 - e0) * float(n_bins), w)
            xs.append(x)
            jac = w if jac is None else jac * w
        val = body(lambda d: xs[d], p, f, dim)
        return val * jac

    wrapped.__name__ = f"adapted_{getattr(body, '__name__', 'body')}"
    return wrapped


def adapt_grid_cols(family):
    """f32[n_fn, dim * (n_bins + 1)] packed bin-edge columns of an
    adapted family, appended after its form's base (and sweep) columns
    in axis-major order."""
    return jnp.asarray(family.params["grid"], jnp.float32).reshape(
        family.n_fn, -1)


def packed_cols(form, family) -> int:
    """Total packed width of ``family`` under ``form`` — the width
    :func:`body_and_packed` produces, sweep, adapt-grid and transform
    columns included.  The fused planner sizes its buckets with this so
    the column layout lives in one module."""
    adapt = family.dim * (family.adapt_bins + 1) if family.adapt_bins else 0
    extra = 2 * family.dim if family.compact else 0
    sweep = len(sweep_col_map(form, family.inner())) if family.swept else 0
    return form.n_cols(family.dim) + sweep + adapt + extra


def body_and_packed(form, family):
    """The (eval body, f32[n_fn, cols]) pair of one family under ``form``.

    The single place swept families grow their substitution wrapper and
    table columns, compactified families their transform wrapper and
    transform columns, and adapted families their importance-map wrapper
    and bin-edge columns — composed, in full, as
    ``adapted_body(compactified_body(swept_body(body)))`` over a
    ``[base][sweep][adapt][transform]`` column layout.  Finite non-swept
    non-adapted families pass through untouched.  Callers (the
    single-family impl and the fused planner) must have
    capability-checked ``form.supports(..., compactified=family.compact,
    sweep=family.swept, adapted=bool(family.adapt_bins))`` first.
    """
    adapt_bins = family.adapt_bins
    core = family.adapt_inner()
    base_cols = form.n_cols(family.dim)
    inner = core.inner()
    if family.swept:
        col_map = sweep_col_map(form, inner)
        body = swept_body(form.body, base_cols, col_map)
        packed = jnp.concatenate([
            jnp.asarray(form.pack_params(inner.sweep_base()), jnp.float32),
            sweep_table_cols(inner)], axis=1)
        core_cols = base_cols + len(col_map)
    else:
        body = form.body
        packed = jnp.asarray(form.pack_params(inner), jnp.float32)
        core_cols = base_cols
    adapt_len = family.dim * (adapt_bins + 1) if adapt_bins else 0
    if family.compact:
        # the transform stage reads past the adapt columns: [..][adapt][transform]
        body = compactified_body(body, core_cols + adapt_len)
    if adapt_bins:
        body = adapted_body(body, core_cols, adapt_bins)
        packed = jnp.concatenate([packed, adapt_grid_cols(family)], axis=1)
    if family.compact:
        packed = jnp.concatenate([packed, transform_cols(core)], axis=1)
    return body, packed


def _fused_kernel(*refs, dim: int, bodies: tuple, sampler: str,
                  has_forms: bool, has_round_base: bool, n_rounds: int):
    """One (function-block, round, sample-block) grid cell.

    Ref order: scalars, fn_ids, [form_ids], [round_base], [dirvecs],
    packed, lo, hi, out.  The SMEM operands other than ``scalars`` are
    whole arrays indexed by the function-block id ``i``: Mosaic refuses
    a rank-1 SMEM block that is neither the whole array nor a multiple
    of 128 words.
      scalars: SMEM u32[4|5] = (k0, k1, sample_offset, n_valid
               [, round_stride — required when n_rounds > 1])
      fn_ids:  SMEM u32[n_fn_pad] global function ids (RNG counters)
      form_ids: SMEM i32[n_blocks] body index per function block
               (multi-form)
      round_base: SMEM u32[n_blocks] additional per-block sample offset
               (fused streams at different refinement depths)
      dirvecs: VMEM u32[dim, 32] Sobol direction vectors (sampler="sobol")
      packed:  VMEM f32[F_BLK, n_cols] form-packed parameters
      lo/hi:   VMEM f32[F_BLK, dim] domain boxes
      out:     VMEM f32[1, F_BLK, 2] this round's running (sum f, sum f^2)
    """
    it = iter(refs)
    scalars_ref = next(it)
    fn_ids_ref = next(it)
    form_ref = next(it) if has_forms else None
    rbase_ref = next(it) if has_round_base else None
    v_ref = next(it) if sampler == "sobol" else None
    packed_ref, lo_ref, hi_ref, out_ref = it

    i = pl.program_id(0)
    j = pl.program_id(2)
    k0 = scalars_ref[0]
    k1 = scalars_ref[1]
    sample_offset = scalars_ref[2]
    n_valid = scalars_ref[3]
    if has_round_base:
        sample_offset = sample_offset + rbase_ref[i]
    if n_rounds > 1:
        # round r's window starts round_stride counters after round r-1's;
        # uint32 adds are exact, so this matches a single-round launch at
        # sample_offset + r * round_stride bit for bit
        r = pl.program_id(1)
        sample_offset = sample_offset + jnp.uint32(r) * scalars_ref[4]

    local_idx = tile_sample_index(j)
    c0 = sample_offset + local_idx          # global sample counter
    valid = local_idx < n_valid

    pts = sobol_tiles(c0, v_ref[...], dim) if sampler == "sobol" else None
    p = packed_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]

    def eval_block(body):
        parts = []
        for f in range(F_BLK):
            fid = fn_ids_ref[i * F_BLK + f]

            def draw(d, f=f, fid=fid):
                c1 = fid * jnp.uint32(rng_lib.DIM_STRIDE) + jnp.uint32(d)
                if sampler == "sobol":
                    # per-(fn, dim) digital shift: same counter plane as
                    # the pure-jnp oracle (core/sobol.shifts_for)
                    shift = rng_lib.random_bits(
                        k0, k1, jnp.uint32(SOBOL_SHIFT_C0), c1)
                    bits = pts[d] ^ shift
                else:
                    bits = rng_lib.random_bits(k0, k1, c0, c1)
                u = rng_lib.bits_to_uniform(bits)
                return lo[f, d] + u * (hi[f, d] - lo[f, d])

            val = body(draw, p, f, dim)
            val = jnp.where(valid, val, 0.0)
            parts.append(jnp.stack([jnp.sum(val), jnp.sum(val * val)]))
        return jnp.stack(parts)            # (F_BLK, 2)

    if has_forms and len(bodies) > 1:
        part = jax.lax.switch(
            form_ref[i], [functools.partial(eval_block, b) for b in bodies])
    else:
        part = eval_block(bodies[0])

    accumulate(j, out_ref, part[None])     # (1, F_BLK, 2) round-r block


@functools.partial(jax.jit, static_argnames=(
    "dim", "n_sample_blocks", "n_rounds", "bodies", "sampler", "interpret",
    "name"))
def fused_mc_pallas(scalars, fn_ids, packed, lo, hi, form_ids=None,
                    round_base=None, dirvecs=None, *, dim: int,
                    n_sample_blocks: int, bodies: tuple, n_rounds: int = 1,
                    sampler: str = "mc", interpret: bool,
                    name: str = "mc_eval_fused"):
    """One pallas_call over a (padded) stack of functions x rounds.

    Args:
      scalars: u32[4] (k0, k1, sample_offset, n_valid) — or u32[5] with a
        trailing ``round_stride`` when ``n_rounds > 1`` (counters round r
        draws start at ``offset + r * round_stride``).
      fn_ids: u32[n_fn_pad] with n_fn_pad % F_BLK == 0.
      packed: f32[n_fn_pad, n_cols] form-packed parameters.
      lo, hi: f32[n_fn_pad, dim] domain boxes.
      form_ids: optional i32[n_fn_pad // F_BLK] per-block body index
        (required when len(bodies) > 1; blocks must be form-homogeneous).
      round_base: optional u32[n_fn_pad // F_BLK] per-block extra sample
        offset, added to ``scalars[2]`` — lets one launch fuse function
        blocks whose sample windows start at different stream depths.
      dirvecs: u32[dim, 32] Sobol direction vectors (sampler="sobol").
      bodies: static tuple of eval bodies (see module docstring).
      n_rounds: consecutive counter windows to evaluate in this launch.
    Returns:
      f32[n_rounds, n_fn_pad, 2] of per-round (sum f, sum f^2) per
      function; each round bit-identical to its own single-round launch.
    """
    n_fn_pad = fn_ids.shape[0]
    assert n_fn_pad % F_BLK == 0
    if len(bodies) > 1 and form_ids is None:
        raise ValueError(
            "multiple eval bodies need per-block form_ids; without them "
            "every block would silently run bodies[0]")
    if n_rounds > 1 and scalars.shape[0] < 5:
        raise ValueError(
            "multi-round launches need scalars[4] = round_stride "
            "(pack_scalars(..., round_stride=...))")
    grid = (n_fn_pad // F_BLK, n_rounds, n_sample_blocks)
    fn_blk = lambda i, r, j: (i, 0)

    # scalars, fn_ids, form_ids and round_base are whole SMEM arrays
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [smem, smem]
    args = [scalars, fn_ids]
    has_forms = form_ids is not None
    if has_forms:
        in_specs.append(smem)
        args.append(form_ids)
    has_round_base = round_base is not None
    if has_round_base:
        in_specs.append(smem)
        args.append(round_base)
    if sampler == "sobol":
        in_specs.append(pl.BlockSpec((dim, 32), lambda i, r, j: (0, 0)))
        args.append(dirvecs)
    n_cols = packed.shape[1]
    in_specs += [
        pl.BlockSpec((F_BLK, n_cols), fn_blk),                    # packed
        pl.BlockSpec((F_BLK, dim), fn_blk),                       # lo
        pl.BlockSpec((F_BLK, dim), fn_blk),                       # hi
    ]
    args += [packed, lo, hi]

    return pl.pallas_call(
        functools.partial(_fused_kernel, dim=dim, bodies=bodies,
                          sampler=sampler, has_forms=has_forms,
                          has_round_base=has_round_base, n_rounds=n_rounds),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, F_BLK, 2), lambda i, r, j: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rounds, n_fn_pad, 2), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # function blocks and rounds write independent output blocks;
            # the sample axis revisits its round's accumulator block and
            # must stay sequential
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*args)


def pack_scalars(key, sample_offset, n_samples, round_stride=None):
    """u32[4] SMEM operand shared by every fused MC kernel — u32[5] with
    the per-round counter stride when the launch is multi-round."""
    parts = [
        jnp.asarray(key[0], jnp.uint32).reshape(()),
        jnp.asarray(key[1], jnp.uint32).reshape(()),
        jnp.asarray(sample_offset, jnp.uint32).reshape(()),
        jnp.asarray(n_samples, jnp.uint32).reshape(()),
    ]
    if round_stride is not None:
        parts.append(jnp.asarray(round_stride, jnp.uint32).reshape(()))
    return jnp.stack(parts)


def probe_operands(dim: int, n_cols: int):
    """Zero-filled abstract-trace operands for one eval body.

    Returns ``(draws, packed)`` shaped exactly like what
    :func:`_fused_kernel` hands a body — ``draws`` is f32[dim, S_ROWS,
    S_LANES] (index ``draws[d]`` to get dimension ``d``'s sample tile)
    and ``packed`` is the f32[F_BLK, n_cols] parameter block.  The
    contract checker (:mod:`repro.analysis.contracts`) traces bodies on
    these to prove purity/dtype/aval invariants without a device.
    """
    return (jnp.zeros((dim, S_ROWS, S_LANES), jnp.float32),
            jnp.zeros((F_BLK, n_cols), jnp.float32))


def make_family_impl(form, sampler: str):
    """Build a registry fast-path callable for one form + sampler.

    The returned impl matches ``direct_mc.family_sums`` semantics exactly:
    same Threefry counters, same uniforms, same estimates (up to f32
    association order) — asserted by the kernel test sweeps.
    """
    from repro.core.direct_mc import SumsState

    def impl(family, n_samples: int, key, *, fn_offset: int = 0,
             sample_offset=0, fn_ids=None,
             interpret: bool | None = None) -> SumsState:
        n_fn, dim = family.n_fn, family.dim
        compact = family.compact
        if not form.supports(dim=dim, sampler=sampler, compactified=compact,
                             sweep=family.swept,
                             adapted=bool(family.adapt_bins)):
            raise ValueError(
                f"kernel {form.name!r} does not support dim={dim} with "
                f"sampler={sampler!r}"
                + (" on a compactified family" if compact else "")
                + (f" swept over {family.swept}" if family.swept else "")
                + (" with an importance grid" if family.adapt_bins else ""))
        if fn_ids is None:
            fn_ids = jnp.uint32(fn_offset) + jnp.arange(n_fn,
                                                        dtype=jnp.uint32)
        interpret = resolve_interpret(interpret)

        n_fn_pad = math.ceil(n_fn / F_BLK) * F_BLK
        pad = n_fn_pad - n_fn
        body, packed = body_and_packed(form, family)
        packed = pad_rows(packed, pad)
        lo = pad_rows(jnp.asarray(family.domains[..., 0], jnp.float32), pad)
        hi = pad_rows(jnp.asarray(family.domains[..., 1], jnp.float32), pad)
        fn_ids = pad_rows(jnp.asarray(fn_ids, jnp.uint32), pad)

        dirvecs = None
        if sampler == "sobol":
            from repro.core.sobol import direction_vectors
            dirvecs = jnp.asarray(direction_vectors(dim))

        n_sample_blocks = max(1, math.ceil(int(n_samples) / S_BLK))
        scalars = pack_scalars(key, sample_offset, n_samples)
        record_launch()
        out = fused_mc_pallas(
            scalars, fn_ids, packed, lo, hi, dirvecs=dirvecs, dim=dim,
            n_sample_blocks=n_sample_blocks, bodies=(body,),
            sampler=sampler, interpret=interpret,
            name=form.name if sampler == "mc" else f"{form.name}@{sampler}")[0]
        return SumsState(s1=out[:n_fn, 0], s2=out[:n_fn, 1],
                         n=jnp.float32(n_samples))

    impl.__name__ = form.name if sampler == "mc" else f"{form.name}@{sampler}"
    impl.form = form
    impl.sampler = sampler
    return impl
