"""Layer 2: jaxpr contract checker for registered kernel forms.

The fused launch path makes three promises it cannot check cheaply at
launch time:

* eval bodies are **pure** — a body that hides a host callback or debug
  print would make per-round sums depend on execution order, breaking
  the WAL's bit-exact replay (KCT001);
* bodies accumulate in **float32** — the ``(s1, s2)`` deposit dtype the
  journal stores exactly, and the only dtype the TPU reduction path is
  fast at (KCT002);
* all bodies fused into one ``(dim, sampler)`` bucket produce
  **identical output avals** — ``lax.switch`` in the fused kernel
  (``template._fused_kernel``) selects between them per function block
  and silently requires matching branch signatures (KCT003);
* a form advertising ``supports_compactified=True`` really does compose
  with ``template.compactified_body`` — otherwise infinite-domain
  families fall back (or worse, miscompute the Jacobian) at launch time
  (KCT004);
* a form declaring ``sweep_cols`` really does compose with
  ``template.swept_body`` — the declared column map must substitute
  cleanly into the packed row (and through the compactified wrapper),
  or parameter sweeps would fail at first launch (KCT005);
* a form advertising ``supports_adapted=True`` really does compose with
  ``template.adapted_body`` — the VEGAS importance-map stage must read
  its packed edge columns and fold the Jacobian cleanly (including
  through the compactified wrapper), or adapted streams would fail (or
  bias the estimate) at their first post-pilot launch (KCT006).

This module proves all six **abstractly**: each registered
:class:`~repro.kernels.registry.KernelForm` body is traced with
``jax.make_jaxpr`` on zero-filled probe operands
(:func:`repro.kernels.template.probe_operands`) for every capability
combination it advertises (sampler × finite/compactified ×
plain/swept × plain/adapted, over a probe dim sweep; the engine never
builds swept+adapted streams, so that combination is not probed).  No
kernel is launched and no device is needed — this runs in CI on CPU in
milliseconds.

:func:`validate_form_registration` packages the same predicates for
eager use at registration time (``registry.register_form``), so a
contract-breaking form raises a named exception where it is defined
instead of failing deep inside ``lax.switch`` at first launch.
"""

from __future__ import annotations

import functools
import inspect

import jax

from repro.analysis.violations import Violation
from repro.kernels import template

# Dimensions each form is probed at: the low dims the paper's example
# suite lives in plus one mid-size dim; each is clipped to the form's
# advertised max_dim (and the Sobol table limit for sampler="sobol").
PROBE_DIMS = (1, 2, 4)

# Importance-grid bins used when probing adapted combos (KCT006).  The
# adapted wrapper unrolls a static per-axis bin loop, so a small probe
# count keeps registration-time traces fast; composition is bin-count
# independent (the column layout is the only thing that scales).
PROBE_BINS = 4

# jaxpr primitive-name fragments that mean "talks to the host".  The
# ``effects`` set catches most of these; the name scan also catches a
# host-talking primitive that declares no effect.
_SIDE_EFFECT_FRAGMENTS = ("callback", "infeed", "outfeed", "debug")


def _body_location(body) -> tuple[str, int]:
    """(file, line) of an eval body, for violation labelling."""
    try:
        code = getattr(body, "__wrapped__", body).__code__
        return code.co_filename.replace("\\", "/"), code.co_firstlineno
    except AttributeError:
        try:
            path = inspect.getsourcefile(body) or "<unknown>"
            _, line = inspect.getsourcelines(body)
            return path.replace("\\", "/"), line
        except (OSError, TypeError):
            return "<unknown>", 0


def _iter_eqns(jaxpr):
    """All equations in a jaxpr, descending into sub-jaxprs (scan/cond/
    switch/pjit bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from _iter_eqns(sub)


def _sub_jaxprs(param):
    if hasattr(param, "jaxpr"):        # ClosedJaxpr
        yield param.jaxpr
    elif hasattr(param, "eqns"):       # raw Jaxpr
        yield param
    elif isinstance(param, (list, tuple)):
        for item in param:
            yield from _sub_jaxprs(item)


@functools.lru_cache(maxsize=512)
def _trace_body(body, dim: int, n_cols: int):
    """(out_avals, closed_jaxpr) of ``body`` on zero probe operands.

    lru_cached on body identity: registration-time validation re-traces
    each registered body against every newcomer sharing a bucket, and
    ``compactified_body`` wrappers are themselves cached, so repeat
    traces are pure cache hits.
    """
    draws, packed = template.probe_operands(dim, n_cols)

    def probe(draws, packed):
        return body(lambda d: draws[d], packed, 0, dim)

    closed = jax.make_jaxpr(probe)(draws, packed)
    return tuple(closed.out_avals), closed


def _probe_dims(form, sampler: str) -> list[int]:
    dims = []
    for dim in PROBE_DIMS:
        if form.supports(dim=dim, sampler=sampler):
            dims.append(dim)
    return dims


def _full_sweep(form, dim: int) -> tuple[str, ...]:
    """The widest sweep the form advertises at ``dim`` — every name in
    its ``sweep_cols`` map, sorted (the order ``swept_over`` produces).
    Probing the full set subsumes every subset: subsets substitute fewer
    columns through the identical wrapper machinery."""
    if form.sweep_cols is None:
        return ()
    return tuple(sorted(form.sweep_cols(dim)))


def _combos(form):
    """Every advertised capability combination: (sampler, compactified,
    swept, adapted, dim) tuples the form claims to support.  ``swept``
    probes the form's full ``sweep_cols`` name set (or stays ``()``);
    ``adapted`` is probed only for non-swept combos, mirroring the
    engine (adapted streams are never swept)."""
    out = []
    for sampler in form.samplers:
        for compact in (False, True):
            if compact and not form.supports_compactified:
                continue
            for dim in _probe_dims(form, sampler):
                for swept in ({(), _full_sweep(form, dim)} if
                              form.supports_swept else {()}):
                    adapt_axis = ((False, True) if
                                  form.supports_adapted and not swept
                                  else (False,))
                    for adapted in adapt_axis:
                        if form.supports(dim=dim, sampler=sampler,
                                         compactified=compact, sweep=swept,
                                         adapted=adapted):
                            out.append((sampler, compact, swept, adapted,
                                        dim))
    return sorted(out)


def _body_for(form, compact: bool, dim: int, swept: tuple[str, ...] = (),
              adapt_bins: int = 0):
    """(body, n_cols) the launch path would use for this combo — the
    sweep wrapper grows one table column per swept parameter column,
    the importance-map wrapper ``dim * (adapt_bins + 1)`` edge columns
    after that, and the compactified wrapper 2*dim transform columns
    last, exactly mirroring ``template.body_and_packed``'s
    ``[base][sweep][adapt][transform]`` composition and layout."""
    body, n_cols = form.body, form.n_cols(dim)
    if swept:
        cols = form.sweep_cols(dim)
        col_map = tuple(int(c) for name in swept for c in cols[name])
        body = template.swept_body(body, n_cols, col_map)
        n_cols += len(col_map)
    adapt_len = dim * (adapt_bins + 1) if adapt_bins else 0
    if compact:
        body = template.compactified_body(body, n_cols + adapt_len)
    if adapt_bins:
        body = template.adapted_body(body, n_cols, adapt_bins)
    n_cols += adapt_len
    if compact:
        n_cols += 2 * dim
    return body, n_cols


def check_form(form) -> list[Violation]:
    """KCT001/KCT002/KCT004/KCT005/KCT006 for one form, over every
    advertised combo."""
    found: list[Violation] = []
    path, line = _body_location(form.body)
    seen: set[tuple] = set()
    for sampler, compact, swept, adapted, dim in _combos(form):
        combo_key = (compact, swept, adapted, dim)  # bodies are sampler-independent
        if combo_key in seen:
            continue
        seen.add(combo_key)
        adapt_bins = PROBE_BINS if adapted else 0
        body, n_cols = _body_for(form, compact, dim, swept, adapt_bins)
        label = (f"{form.name}[dim={dim}"
                 + (", compactified" if compact else "")
                 + (f", swept={','.join(swept)}" if swept else "")
                 + (", adapted" if adapted else "") + "]")
        try:
            out_avals, closed = _trace_body(body, dim, n_cols)
        except Exception as exc:  # noqa: BLE001 - any trace failure is the finding
            rule = ("KCT006" if adapted else
                    "KCT005" if swept else
                    "KCT004" if compact else "KCT001")
            found.append(Violation(
                rule=rule, path=path, line=line,
                message=f"{label} fails to trace: {exc}"))
            continue

        effects = getattr(closed, "effects", frozenset())
        if effects:
            found.append(Violation(
                rule="KCT001", path=path, line=line,
                message=f"{label} jaxpr carries effects {sorted(map(str, effects))}"))
        for eqn in _iter_eqns(closed.jaxpr):
            prim = eqn.primitive.name
            if any(frag in prim for frag in _SIDE_EFFECT_FRAGMENTS):
                found.append(Violation(
                    rule="KCT001", path=path, line=line,
                    message=f"{label} jaxpr contains side-effecting "
                            f"primitive {prim!r}"))

        for aval in out_avals:
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) != "float32":
                found.append(Violation(
                    rule="KCT002", path=path, line=line,
                    message=f"{label} accumulates in {dtype} (the (s1, s2) "
                            "deposit contract is float32)"))
        shapes = [getattr(a, "shape", None) for a in out_avals]
        if shapes != [(template.S_ROWS, template.S_LANES)]:
            found.append(Violation(
                rule=("KCT006" if adapted else
                      "KCT005" if swept else
                      "KCT004" if compact else "KCT002"),
                path=path, line=line,
                message=f"{label} returns avals shaped {shapes}, expected "
                        f"one ({template.S_ROWS}, {template.S_LANES}) tile"))
    return found


def bucket_avals(form, sampler: str, dim: int):
    """Output avals of the (body, packed-width) the fused planner would
    put in the (dim, sampler) bucket for this form's *finite* families.
    Returns None if the form doesn't trace (check_form reports that)."""
    body, n_cols = _body_for(form, False, dim)
    try:
        out_avals, _ = _trace_body(body, dim, n_cols)
    except Exception:  # noqa: BLE001
        return None
    return tuple((getattr(a, "shape", None), str(getattr(a, "dtype", "?")))
                 for a in out_avals)


def check_bucket_uniformity(forms) -> list[Violation]:
    """KCT003: identical output avals across all forms sharing a
    (dim, sampler) bucket — the ``lax.switch`` branch precondition."""
    found: list[Violation] = []
    buckets: dict[tuple, list] = {}
    for form in forms:
        for sampler in form.samplers:
            for dim in _probe_dims(form, sampler):
                buckets.setdefault((dim, sampler), []).append(form)
    for (dim, sampler), members in sorted(buckets.items()):
        sigs = []
        for form in members:
            avals = bucket_avals(form, sampler, dim)
            if avals is not None:
                sigs.append((form, avals))
        if len({avals for _, avals in sigs}) <= 1:
            continue
        majority = max({avals for _, avals in sigs},
                       key=lambda a: sum(1 for _, x in sigs if x == a))
        for form, avals in sigs:
            if avals != majority:
                path, line = _body_location(form.body)
                found.append(Violation(
                    rule="KCT003", path=path, line=line,
                    message=f"{form.name} produces avals {avals} in the "
                            f"(dim={dim}, sampler={sampler!r}) bucket; "
                            f"other bucket members produce {majority} — "
                            "lax.switch branches must match"))
    return found


def check_forms(forms) -> list[Violation]:
    """All Layer-2 rules over an explicit form collection."""
    found: list[Violation] = []
    for form in forms:
        found.extend(check_form(form))
    found.extend(check_bucket_uniformity(forms))
    return found


def check_registered_forms() -> list[Violation]:
    """All Layer-2 rules over every registered form (CI entry point).

    Coverage is total by construction: :func:`check_form` enumerates
    every (sampler, compactified, probe-dim) combination each form
    advertises, and :func:`check_bucket_uniformity` visits every
    (dim, sampler) bucket those combinations induce.
    """
    from repro.kernels import registry
    return check_forms(registry.forms())


def validate_form_registration(form, existing) -> None:
    """Eager registration-time gate: raise ValueError if ``form`` breaks
    a kernel contract on its own or against already-registered forms.

    Called by ``registry.register_form`` before the registry mutates, so
    a bad form never becomes visible.  ``existing`` is the iterable of
    already-registered KernelForms to check bucket uniformity against.
    """
    own = check_form(form)
    if own:
        raise ValueError(
            f"kernel form {form.name!r} violates kernel contracts:\n"
            + "\n".join(str(v) for v in own))
    for sampler in form.samplers:
        for dim in _probe_dims(form, sampler):
            new_avals = bucket_avals(form, sampler, dim)
            if new_avals is None:
                continue
            for other in existing:
                if sampler not in other.samplers or not other.supports(
                        dim=dim, sampler=sampler):
                    continue
                other_avals = bucket_avals(other, sampler, dim)
                if other_avals is not None and other_avals != new_avals:
                    raise ValueError(
                        f"kernel form {form.name!r} produces output avals "
                        f"{new_avals} in the (dim={dim}, "
                        f"sampler={sampler!r}) bucket, but registered form "
                        f"{other.name!r} produces {other_avals}: lax.switch "
                        "fusion requires identical branch signatures "
                        "[KCT003]")
