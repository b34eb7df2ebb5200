"""Compactified families on the fused kernel path: cross-path parity.

Infinite-domain integrands reach the kernels through a static per-axis
transform (kind + shift packed as parameter columns) applied by a
wrapper stage around the registered eval body
(``template.compactified_body``).  The invariants asserted here:

* **parity** — fused kernel sums match the chunked JAX path (both apply
  the identical ``domains.apply_transform``; only f32 fold order
  differs) for fully-infinite and half-infinite boxes, mc and sobol,
  single-device and mesh;
* **accuracy** — kernel-path estimates hit the analytic Gaussian values
  over R^d and [0, inf)^d within their reported stderr;
* **no fallback** — a mixed finite/infinite batch buckets into fused
  launches with zero families left to the chunked path, at the planner
  level (``plan.unfused``) and through the live service engine
  (launch count == buckets, ``RoundBatcher.fallback_rounds == 0``).
"""

import jax
import numpy as np
import pytest

from repro.core import (MultiFunctionSpec, family_sums, finalize,
                        gaussian_analytic, gaussian_family, harmonic_family)
from repro.core import rng as rng_lib
from repro.kernels import template
from repro.kernels.mc_eval import multi

KEY = rng_lib.fold_key(11, 0)
N = 4096 + 321   # off a block multiple: exercises the tail mask
R = 4096


def gaussian_inf(n, dim):
    return gaussian_family(n, dim, lo=-np.inf, hi=np.inf)


def gaussian_half(n, dim):
    return gaussian_family(n, dim, lo=0.0, hi=np.inf)


def harmonic_half(n, dim):
    return harmonic_family(n, dim, lo=0.0, hi=np.inf)


# -- fused vs chunked parity --------------------------------------------------

@pytest.mark.parametrize("sampler", ["mc", "sobol"])
@pytest.mark.parametrize("maker", [gaussian_inf, gaussian_half])
def test_fused_matches_chunked(maker, sampler):
    """Kernel and chunked paths draw the same counters and apply the
    same transform — sums agree up to f32 association order."""
    fam = maker(5, 3).compactified()
    assert fam.compact and fam.kernel is not None
    template.reset_launch_count()
    k = family_sums(fam, N, KEY, use_kernel=True, sampler=sampler)
    assert template.launch_count() == 1, "compactified family fell back"
    c = family_sums(fam, N, KEY, use_kernel=False, sampler=sampler,
                    chunk=1024)
    np.testing.assert_allclose(np.asarray(k.s1), np.asarray(c.s1),
                               rtol=5e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(k.s2), np.asarray(c.s2),
                               rtol=5e-3, atol=1e-2)


@pytest.mark.parametrize("sampler", ["mc", "sobol"])
def test_harmonic_half_infinite_same_transform(sampler):
    """Harmonic over [0, inf)^d: both paths apply the same transform.

    The integral diverges and the dominant samples evaluate cos at
    phases ~1e6, where f32 phase accumulation error alone is O(0.1 rad)
    — so *any* two f32 evaluation orders disagree at O(10%) on the sums
    and elementwise parity is ill-posed.  What IS well-posed: the
    Jacobian-amplified magnitude.  A missing or wrong transform moves s2
    by orders of magnitude; same-order agreement pins the wrapper stage
    without asserting meaningless digits.
    """
    fam = harmonic_half(5, 3).compactified()
    template.reset_launch_count()
    k = family_sums(fam, N, KEY, use_kernel=True, sampler=sampler)
    assert template.launch_count() == 1, "compactified family fell back"
    c = family_sums(fam, N, KEY, use_kernel=False, sampler=sampler,
                    chunk=1024)
    ks2, cs2 = np.asarray(k.s2), np.asarray(c.s2)
    assert np.all(ks2 > 0) and np.all(cs2 > 0)
    np.testing.assert_allclose(np.log10(ks2), np.log10(cs2), atol=0.5)


def test_compactified_offsets_match_chunked():
    """fn_offset / sample_offset address the same counter space on the
    wrapped body (the service cache's resume invariant)."""
    fam = gaussian_inf(4, 2).compactified()
    k = family_sums(fam, R, KEY, fn_offset=37, sample_offset=5 * R,
                    use_kernel=True)
    c = family_sums(fam, R, KEY, fn_offset=37, sample_offset=5 * R,
                    use_kernel=False, chunk=1024)
    np.testing.assert_allclose(np.asarray(k.s1), np.asarray(c.s1),
                               rtol=1e-4, atol=1e-3)


# -- analytic accuracy --------------------------------------------------------

@pytest.mark.parametrize("sampler", ["mc", "sobol"])
@pytest.mark.parametrize("half", [False, True])
def test_gaussian_analytic_values(half, sampler):
    """int exp(-|x|^2 / 2 sigma^2) over R^d (and its positive orthant)
    lands on (sigma sqrt(2 pi))^d within the reported stderr."""
    maker = gaussian_half if half else gaussian_inf
    fam = maker(3, 3).compactified()
    res = finalize(fam, family_sums(fam, 16 * R, KEY, use_kernel=True,
                                    sampler=sampler))
    exact = gaussian_analytic(3, 3, half=half)
    assert np.all(np.abs(np.asarray(res.mean) - exact)
                  <= 6 * np.asarray(res.stderr) + 1e-3), (res.mean, exact)


# -- fusion: mixed finite / infinite buckets ----------------------------------

def _mixed_spec():
    return MultiFunctionSpec.from_families([
        harmonic_family(4, 3),
        gaussian_inf(3, 3).compactified(),
        gaussian_half(2, 3).compactified(),
    ])


def test_mixed_bucket_no_fallback():
    """Finite and compactified families of one dim share ONE launch."""
    spec = _mixed_spec()
    plan = multi.plan_spec(spec)
    assert plan.unfused == ()
    assert plan.n_launches == 1
    # the wrapper gives the compactified gaussians a distinct switch body
    assert len(plan.buckets[0].bodies) == 2
    out = multi.eval_plan(plan, N, KEY)
    offs = spec.offsets()
    for i, fam in enumerate(spec.families):
        ref = family_sums(fam, N, KEY, fn_offset=offs[i], use_kernel=False,
                          chunk=1024)
        np.testing.assert_allclose(np.asarray(out[i].s1),
                                   np.asarray(ref.s1), rtol=1e-4, atol=1e-2)


def test_compactified_wrapper_identity_is_shared():
    """Two plans of the same compactified form reuse ONE wrapped body, so
    buckets dedupe bodies and the jit compile cache keys stay stable."""
    a = multi.plan_spec(MultiFunctionSpec.from_families(
        [gaussian_inf(3, 3).compactified()]))
    b = multi.plan_spec(MultiFunctionSpec.from_families(
        [gaussian_half(2, 3).compactified()]))
    assert a.buckets[0].bodies == b.buckets[0].bodies


def test_multiround_compactified_bit_identical():
    """R rounds of a mixed finite/infinite bucket in one launch: each
    round bit-identical to its own single-round launch."""
    plan = multi.plan_spec(_mixed_spec())
    fused = multi.split_rounds(multi.eval_plan_rounds(
        plan, R, 3, KEY, start_rounds={0: 0, 1: 0, 2: 0}), R)
    for r in range(3):
        single = multi.eval_plan(plan, R, KEY, sample_offset=r * R)
        for fam in single:
            np.testing.assert_array_equal(np.asarray(fused[fam][r].s1),
                                          np.asarray(single[fam].s1))
            np.testing.assert_array_equal(np.asarray(fused[fam][r].s2),
                                          np.asarray(single[fam].s2))


def test_sharded_compactified_matches_single_device():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    plan = multi.plan_spec(_mixed_spec())
    single = multi.eval_plan(plan, R, KEY)
    sharded = multi.sharded_eval_plan(plan, R, KEY, mesh)
    for i in single:
        np.testing.assert_array_equal(np.asarray(single[i].s1),
                                      np.asarray(sharded[i].s1))
        np.testing.assert_array_equal(np.asarray(single[i].s2),
                                      np.asarray(sharded[i].s2))
    starts = {0: 2, 1: 0, 2: 1}
    fused = multi.split_rounds(multi.eval_plan_rounds(
        plan, R, 2, KEY, start_rounds=starts), R)
    shr = multi.split_rounds(multi.sharded_eval_plan_rounds(
        plan, R, 2, KEY, mesh, start_rounds=starts), R)
    for i in fused:
        for r in range(2):
            np.testing.assert_array_equal(np.asarray(fused[i][r].s1),
                                          np.asarray(shr[i][r].s1))


def test_unregistered_compactified_family_still_falls_back():
    """A compactified family without a registered form keeps the chunked
    path (capability miss, not a crash)."""
    import jax.numpy as jnp
    from repro.core.integrand import IntegrandFamily
    fam = IntegrandFamily(
        fn=lambda x, p: p["s"] * jnp.exp(-jnp.sum(jnp.abs(x), -1)),
        params={"s": jnp.ones(3)},
        domains=jnp.asarray(np.broadcast_to([0.0, np.inf],
                                            (3, 2, 2)).copy()),
        name="exp").validate().compactified()
    plan = multi.plan_spec(MultiFunctionSpec.from_families([fam]))
    assert plan.unfused == (0,)
    template.reset_launch_count()
    sums = family_sums(fam, R, KEY, use_kernel=True)
    assert template.launch_count() == 0
    assert np.all(np.isfinite(np.asarray(sums.s1)))


# -- service engine: infinite-domain requests stay fused ----------------------

def test_service_mixed_batch_entirely_fused():
    """A mixed batch of finite and infinite-domain requests is served by
    fused kernels only: launches == (dim, sampler) buckets, zero chunked
    fallbacks, and the infinite-domain answers are right."""
    from repro.service import IntegrationEngine, IntegrationRequest
    engine = IntegrationEngine(seed=0, round_samples=R,
                               max_rounds_per_wave=8)
    reqs = [
        IntegrationRequest.make([gaussian_family(4, 3)], n_samples=2 * R),
        IntegrationRequest.make([gaussian_inf(4, 3)], n_samples=2 * R),
        IntegrationRequest.make([gaussian_half(3, 2)], n_samples=2 * R),
        IntegrationRequest.make([harmonic_family(4, 2)], n_samples=2 * R),
    ]
    tickets = [engine.submit(r) for r in reqs]
    template.reset_launch_count()
    while engine.step():
        pass
    assert template.launch_count() == 2          # dims {2, 3} -> 2 buckets
    assert engine.batcher.fallback_rounds == 0
    results = [engine.poll(t) for t in tickets]
    assert all(r is not None for r in results)
    exact = gaussian_analytic(4, 3)
    assert np.all(np.abs(results[1].means - exact)
                  <= 6 * results[1].stderrs + 1e-3)


def test_service_infinite_domain_warm_restart_bit_identical(tmp_path):
    """An infinite-domain stream journals, restarts and tops up exactly
    like a finite one now that it runs on the kernel path."""
    from repro.service import IntegrationClient, IntegrationEngine
    fams = [gaussian_inf(4, 3)]
    e1 = IntegrationEngine(seed=0, round_samples=R,
                           state_dir=str(tmp_path))
    first = IntegrationClient(e1).integrate(fams, n_samples=2 * R)
    # no close(): the journal is all that survives the "SIGKILL"
    e2 = IntegrationEngine(seed=0, round_samples=R,
                           state_dir=str(tmp_path))
    template.reset_launch_count()
    again = IntegrationClient(e2).integrate(fams, n_samples=2 * R)
    assert template.launch_count() == 0 and again.served_from_cache
    np.testing.assert_array_equal(first.means, again.means)
    # top-up pays only the delta round, still fused
    topped = IntegrationClient(e2).integrate(fams, n_samples=3 * R)
    assert template.launch_count() == 1
    clean = IntegrationClient(
        IntegrationEngine(seed=0, round_samples=R)).integrate(
            fams, n_samples=3 * R)
    np.testing.assert_array_equal(topped.means, clean.means)
