#!/usr/bin/env python3
"""Smoke test of the integration service on a TPU chip.

    python chip_smoke.py              # one chip: the service's main path
    python chip_smoke.py --chips 4    # four chips: the mesh path only

One chip.  One process drives :class:`repro.service.IntegrationEngine`
with the fused Pallas kernel (``use_kernel=True``) through this request
mix:

* the paper batch: 10^3 harmonic integrands of dim 4 at 8 rounds of
  131,072 samples (about 10^6 samples each);
* one request of each registered form, holding that form at dims 2-4;
* one compactified Gaussian over R^3, one ``adaptive=True`` Genz corner
  peak and one parameter sweep, so every wrapper stage of the kernel
  template (compactified, adapted, swept) runs compiled.

It then checks:

* the harmonic estimates against ``harmonic_analytic``, and the
  adaptive corner peak against its closed form: every
  ``|estimate - exact| <= PULL_BAND * stderr``;
* a subset of the functions of every request against the chunked
  pure-jnp path (``use_kernel=False``) drawing the same counters:
  ``|fused - chunked| <= F32_STDERR_FRACTION * stderr + F32_RTOL *
  |chunked|``.  Same counters make the two differ by f32 rounding
  only; different counters would differ by about one stderr;
* a resubmit of the paper batch is served from the cache with 0 kernel
  launches.

Four chips (``--chips 4``).  Builds the ``serve_integrals --mesh`` mesh
over the four chips, serves the paper batch through an engine on it,
and compares it with the same batch served on one device in the same
process, within the same f32 tolerance.  Nothing else runs.

Either run fails, printing no result line, when JAX finds no TPU, when
a kernel would run interpreted, when a round fell back to the chunked
path, when a request completed as ``RequestFailed``, when a wave was
retried (``zmc_wave_restarts_total > 0``: the engine's retry loop turns
a compile error into failed requests), or when a check fails.  Earlier
lines report compile and run seconds on the host clock, launches,
waves, the device kind and the worst pull.  The last line of standard
output is one JSON object: ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# |estimate - exact| <= PULL_BAND * stderr for every analytic check: over
# 10^3 Gaussian pulls the largest is about 3.3, and 5 is exceeded with
# probability below 10^-3.
PULL_BAND = 5.0
# fused vs chunked on the same counters: f32 rounding only
F32_STDERR_FRACTION = 0.02
F32_RTOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Request-mix sizes.  :data:`FULL` is what the chip runs."""
    paper_fns: int = 1000          # harmonic integrands in the paper batch
    paper_dim: int = 4
    round_samples: int = 131072
    rounds: int = 8                # rounds * round_samples ~ 10^6
    form_fns: int = 16             # functions per family elsewhere
    sweep_points: int = 8          # per grid axis (8 x 8 points)
    adaptive_target: float = 1e-5  # stderr target of the adaptive request
    check_fns: int = 4             # functions per family checked vs chunked

    @property
    def n_samples(self) -> int:
        return self.rounds * self.round_samples


FULL = Sizes()


class SmokeFailure(RuntimeError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def paper_request(sz: Sizes):
    from repro.core import harmonic_family
    from repro.service import IntegrationRequest
    return IntegrationRequest.make(
        [harmonic_family(sz.paper_fns, sz.paper_dim)], n_samples=sz.n_samples)


def request_mix(sz: Sizes) -> dict:
    """Named requests: the paper batch, each form at dims 2-4, and the
    wrapper-stage requests."""
    import numpy as np

    from repro.core import (abs_sum_family, gaussian_family,
                            harmonic_family)
    from repro.core import genz
    from repro.service import IntegrationRequest, SweepRequest

    n, dims = sz.form_fns, (2, 3, 4)
    forms = {
        "harmonic": lambda d: harmonic_family(n, d),
        "abs_sum": lambda d: abs_sum_family(n, d, np.linspace(0.5, 2.0, n)),
        "gaussian": lambda d: gaussian_family(n, d),
        "genz_osc": lambda d: genz.oscillatory(n, d)[0],
        "genz_corner": lambda d: genz.corner_peak(n, d)[0],
    }
    reqs = {"paper": paper_request(sz)}
    for name, make in forms.items():
        reqs[name] = IntegrationRequest.make(
            [make(d) for d in dims], n_samples=sz.n_samples)
    reqs["compactified"] = IntegrationRequest.make(
        [gaussian_family(n, 3, lo=-np.inf, hi=np.inf)],
        n_samples=sz.n_samples)
    reqs["adaptive"] = IntegrationRequest.make(
        [genz.corner_peak(n, 3)[0]], target_stderr=sz.adaptive_target,
        adaptive=True)
    k = sz.sweep_points
    reqs["sweep"] = SweepRequest.make(
        harmonic_family(1, 2), {"a": np.linspace(0.5, 2.0, k),
                                "b": np.linspace(-1.0, 1.0, k)},
        n_samples=sz.n_samples)
    return reqs


def make_engine(sz: Sizes, mesh=None, obs=None):
    from repro.service import IntegrationEngine
    return IntegrationEngine(seed=0, round_samples=sz.round_samples,
                             use_kernel=True, mesh=mesh,
                             max_rounds_per_wave=sz.rounds, obs=obs)


def serve(engine, requests: dict) -> dict:
    """Submit every request, drive waves until none is left, and return
    the results by name.  Fails on any ``RequestFailed``."""
    tickets = {name: engine.submit(req) for name, req in requests.items()}
    while engine.step():
        pass
    out = {}
    for name, ticket in tickets.items():
        res = engine.poll(ticket)
        _require(res is not None, f"request {name!r} was never served")
        _require(not res.failed, f"request {name!r} failed: {res}")
        out[name] = res
    return out


def engine_health(engine) -> None:
    """The engine served everything fused, first time, with no retry."""
    _require(engine.batcher.fallback_rounds == 0,
             f"{engine.batcher.fallback_rounds} rounds fell back to the "
             "chunked path")
    restarts = engine.obs.m["restarts"].value()
    _require(restarts == 0, f"zmc_wave_restarts_total = {restarts:g}")
    _require(engine.stats.failed == 0,
             f"{engine.stats.failed} requests failed")


def worst_pull(means, stderrs, exact) -> float:
    import numpy as np
    pulls = np.abs(np.asarray(means, np.float64) - exact) / np.maximum(
        np.asarray(stderrs, np.float64), 1e-30)
    return float(pulls.max())


def check_f32(label: str, got, ref, stderr) -> float:
    """``got`` and ``ref`` drew the same counters; returns the largest
    ``|got - ref| / tol`` (at most 1 when the check passes)."""
    import numpy as np
    got, ref, stderr = (np.asarray(x, np.float64) for x in (got, ref, stderr))
    tol = F32_STDERR_FRACTION * stderr + F32_RTOL * np.abs(ref)
    diff = np.abs(got - ref)
    _require(bool(np.all(diff <= tol)),
             f"{label}: results disagree beyond f32 rounding: "
             f"max |diff| {diff.max():.3e}, tol at that point "
             f"{tol[np.argmax(diff - tol)]:.3e}")
    return float((diff / np.maximum(tol, 1e-30)).max())


def check_against_chunked(engine, results: dict, sz: Sizes) -> float:
    """The first ``check_fns`` functions of every stream behind every
    result, re-evaluated on the chunked pure-jnp path at the stream's own
    counters (key, fn offset, samples 0..n)."""
    import jax
    import numpy as np

    from repro.core import direct_mc

    worst = 0.0
    for name, res in results.items():
        for chash in res.stream_ids:
            entry = engine.cache.get(chash)
            k = min(sz.check_fns, entry.n_fn)
            fam = jax.tree.map(lambda leaf: leaf[:k], entry.family)
            sampler = chash.rsplit(":", 1)[1]
            sums = direct_mc.family_sums(
                fam, entry.n, engine.key, fn_offset=entry.fn_offset,
                use_kernel=False, sampler=sampler)
            ref = direct_mc.finalize(fam, sums)
            fused = entry.finalize()
            worst = max(worst, check_f32(
                f"{name} stream {chash[:16]}",
                np.asarray(fused.mean)[:k], np.asarray(ref.mean),
                np.asarray(fused.stderr)[:k]))
    return worst


def device_line() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def one_chip(sz: Sizes = FULL, obs=None) -> None:
    """Serve the request mix on one device and check every result."""
    import numpy as np

    from repro.core import harmonic_analytic
    from repro.core import genz
    from repro.kernels import template

    engine = make_engine(sz, obs=obs)
    reqs = request_mix(sz)
    template.reset_launch_count()
    t0 = time.perf_counter()
    results = serve(engine, reqs)
    run_s = time.perf_counter() - t0
    launches = template.launch_count()
    engine_health(engine)
    print(f"served {len(results)} requests "
          f"({sum(r.n_fn_total for r in results.values())} integrands) in "
          f"{engine.stats.waves} waves, {launches} kernel launches, "
          f"{run_s:.3f} s host clock (compiles included)")

    paper = results["paper"]
    pull = worst_pull(paper.means, paper.stderrs,
                      harmonic_analytic(sz.paper_fns, sz.paper_dim))
    print(f"paper batch: {paper.n_fn_total} harmonic integrands x "
          f"{paper.n_per_family[0]} samples; worst pull vs analytic "
          f"{pull:.3f} (band {PULL_BAND:g})")
    _require(pull <= PULL_BAND, f"paper batch pull {pull:.3f} > {PULL_BAND}")

    adapt = results["adaptive"]
    exact = genz.corner_peak(sz.form_fns, 3)[1]
    a_pull = worst_pull(adapt.means, adapt.stderrs, exact)
    print(f"adaptive corner peak: {adapt.n_per_family[0]} samples, max "
          f"stderr {float(np.max(adapt.stderrs)):.3e} (target "
          f"{sz.adaptive_target:g}), worst pull vs exact {a_pull:.3f}, "
          f"{engine.obs.m['grid_refits'].value():g} grid refits")
    _require(a_pull <= PULL_BAND,
             f"adaptive corner peak pull {a_pull:.3f} > {PULL_BAND}")
    _require(float(np.max(adapt.stderrs)) <= sz.adaptive_target,
             "adaptive request served above its stderr target")

    worst = check_against_chunked(engine, results, sz)
    print(f"fused vs chunked on the same counters: worst |diff| is "
          f"{worst:.3f} of the limit ({F32_STDERR_FRACTION:g} stderr "
          f"+ {F32_RTOL:g} relative)")

    before = template.launch_count()
    warm = serve(engine, {"paper": paper_request(sz)})["paper"]
    warm_launches = template.launch_count() - before
    print(f"warm resubmit of the paper batch: {warm_launches} launches, "
          f"served_from_cache={warm.served_from_cache}")
    _require(warm.served_from_cache and warm_launches == 0,
             "warm resubmit of the paper batch launched kernels")
    _require(np.array_equal(warm.means, paper.means),
             "warm resubmit returned different estimates")
    engine_health(engine)


def four_chips(sz: Sizes = FULL, obs=None) -> None:
    """Serve the paper batch on the ``serve_integrals --mesh`` mesh and on
    one device; the two must agree to f32 rounding."""
    import jax

    from repro.core import harmonic_analytic
    from repro.kernels import template
    from repro.launch.mesh import make_mesh_for, mesh_info

    n = len(jax.devices())
    _require(n == 4, f"--chips 4 needs 4 devices; JAX has {n}")
    mesh = make_mesh_for(model_parallel=2)   # serve_integrals --mesh on 4
    print(f"mesh: {mesh_info(mesh)}")

    results = {}
    for label, m in (("mesh", mesh), ("one device", None)):
        engine = make_engine(sz, mesh=m, obs=obs)
        template.reset_launch_count()
        t0 = time.perf_counter()
        res = serve(engine, {"paper": paper_request(sz)})["paper"]
        run_s = time.perf_counter() - t0
        engine_health(engine)
        pull = worst_pull(res.means, res.stderrs,
                          harmonic_analytic(sz.paper_fns, sz.paper_dim))
        print(f"{label}: {res.n_fn_total} integrands x "
              f"{res.n_per_family[0]} samples in {engine.stats.waves} "
              f"waves, {template.launch_count()} launches, {run_s:.3f} s "
              f"host clock (compiles included); worst pull vs analytic "
              f"{pull:.3f}")
        _require(pull <= PULL_BAND, f"{label} pull {pull:.3f} > {PULL_BAND}")
        results[label] = res
    worst = check_f32("mesh vs one device", results["mesh"].means,
                      results["one device"].means,
                      results["one device"].stderrs)
    print(f"mesh vs one device: worst |diff| is {worst:.3f} of the limit "
          f"({F32_STDERR_FRACTION:g} stderr + {F32_RTOL:g} relative)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path over four chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    from repro.kernels.pallas_compat import should_interpret
    from repro.obs import Observability

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, not "
              "a TPU", file=sys.stderr)
        return 2
    if should_interpret():
        print("chip_smoke: kernels would run interpreted", file=sys.stderr)
        return 2
    dev = device_line()
    print(f"device: {dev['kind']} x {dev['count']} ({dev['platform']}); "
          f"compile cache: {cache_dir}")

    # every engine shares one bundle: its zmc_backend_compiles_total and
    # zmc_compile_seconds count the whole run's compiles
    obs = Observability.disabled()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(obs=obs)
        else:
            one_chip(obs=obs)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        obs.close()
    secs = obs.m["compile_seconds"]
    print(f"compile: {obs.m['backend_compiles'].value():g} backend "
          f"compiles; seconds by phase: "
          + ", ".join(f"{ph} {secs.sum(phase=ph):.3f}"
                      for ph in ("trace", "lower", "backend"))
          + f"; total {time.perf_counter() - t0:.3f} s host clock")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
