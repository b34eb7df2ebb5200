"""The chip's compiler accepts the main path's kernels.

Interpret mode runs every other kernel test on the CPU, and it accepts
things Mosaic refuses (rank-1 SMEM blocks that are not whole arrays, a
uint32 -> float32 cast).  Here each kernel is compiled for a described
TPU v5e chip that is not attached: nothing runs, so these tests say
nothing about results or times, only that the chip's compiler takes the
kernel.  Dims stay <= 3: a dim-8 bucket takes tens of seconds to
compile.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and a test file that decided
at import whether its tests exist would give parallel workers different
collections.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (MultiFunctionSpec, abs_sum_family, adaptive,
                        gaussian_family, harmonic_family)
from repro.core import genz
from repro.core.sobol import direction_vectors
from repro.kernels import template
from repro.kernels.mc_eval import multi
from repro.kernels.moments.kernel import moments_pallas

ROUND_SAMPLES = 131072          # the paper batch's round quantum


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise write its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            jax.config.update("jax_enable_compilation_cache", cache_was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _sds(x, sharding):
    x = jnp.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _compile_plan(plan, one_chip, *, n_rounds):
    """Compile every bucket of ``plan`` as the service launches it: a
    multi-round window with per-block ``round_base`` offsets."""
    assert plan.buckets
    for b in plan.buckets:
        start = {sl.family_index: sl.family_index for sl in b.slices}
        round_base = multi._round_base_for(b, start, ROUND_SAMPLES)
        dirvecs = (_sds(direction_vectors(b.dim), one_chip)
                   if plan.sampler == "sobol" else None)
        scalars = template.pack_scalars((1, 2), 0, ROUND_SAMPLES,
                                        round_stride=ROUND_SAMPLES)
        compiled = template.fused_mc_pallas.lower(
            _sds(scalars, one_chip), _sds(b.fn_ids, one_chip),
            _sds(b.packed, one_chip), _sds(b.lo, one_chip),
            _sds(b.hi, one_chip),
            form_ids=(None if b.form_ids is None
                      else _sds(b.form_ids, one_chip)),
            round_base=_sds(round_base, one_chip), dirvecs=dirvecs,
            dim=b.dim, n_sample_blocks=ROUND_SAMPLES // template.S_BLK,
            bodies=b.bodies, n_rounds=n_rounds, sampler=plan.sampler,
            interpret=False, name=b.name).compile()
        assert "tpu_custom_call" in compiled.as_text(), b.name


def test_mixed_five_form_bucket_compiles(one_chip):
    n, dim = 20, 3
    spec = MultiFunctionSpec.from_families([
        harmonic_family(n, dim),
        abs_sum_family(n, dim, np.linspace(0.5, 2.0, n)),
        gaussian_family(n, dim),
        genz.oscillatory(n, dim)[0],
        genz.corner_peak(n, dim)[0]])
    plan = multi.plan_spec(spec)
    (bucket,) = plan.buckets
    assert len(bucket.bodies) == 5 and bucket.form_ids is not None
    _compile_plan(plan, one_chip, n_rounds=4)


def test_sobol_bucket_compiles(one_chip):
    spec = MultiFunctionSpec.from_families([harmonic_family(20, 3)])
    _compile_plan(multi.plan_spec(spec, sampler="sobol"), one_chip,
                  n_rounds=2)


def test_wrapper_stage_bucket_compiles(one_chip):
    """Compactified, adapted and swept families fused into one bucket."""
    dim = 2
    corner = genz.corner_peak(8, dim)[0]
    # 4 bins: the adapted stage unrolls a per-bin loop, and the stage's
    # code is the same at any bin count
    edges = adaptive.initial_edges(np.asarray(corner.domains), 4)
    spec = MultiFunctionSpec.from_families([
        gaussian_family(8, dim, lo=-np.inf, hi=np.inf).compactified(),
        corner.adapted(edges),
        harmonic_family(1, dim).swept_over(
            {"a": np.linspace(0.5, 2.0, 16)})])
    plan = multi.plan_spec(spec)
    (bucket,) = plan.buckets
    assert len(bucket.bodies) == 3
    _compile_plan(plan, one_chip, n_rounds=2)


def test_moments_kernel_compiles(one_chip):
    compiled = moments_pallas.lower(
        jax.ShapeDtypeStruct((1024, 4096), jnp.float32, sharding=one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
