"""8-fake-device program: sharded MC statistically valid + mesh-invariant
sum merging; compressed_psum sanity. Run by test_multidevice.py."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..",
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (MultiFunctionSpec, ZMCMultiFunctions,
                        harmonic_analytic, harmonic_family)
from repro.launch.mesh import make_mesh_for

mesh = make_mesh_for(model_parallel=2)            # (data=4, model=2)
spec = MultiFunctionSpec.from_families([harmonic_family(10, 4)])
zm = ZMCMultiFunctions(spec, n_samples=200_000, seed=5, mesh=mesh)
r = zm.evaluate(num_trials=2)
exact = harmonic_analytic(10, 4)
pulls = np.abs(r.trial_mean - exact) / np.maximum(r.stderrs.mean(0), 1e-12)
assert np.all(pulls < 5.0), pulls

# mesh-shape invariance of the estimate (same counters, same totals)
mesh2 = make_mesh_for(model_parallel=4)           # (data=2, model=4)
zm2 = ZMCMultiFunctions(spec, n_samples=200_000, seed=5, mesh=mesh2)
r2 = zm2.evaluate(num_trials=1)
# sample partition differs (4 vs 2 sample shards) -> statistically equal
assert np.all(np.abs(r2.means[0] - r.means[0])
              <= 6 * np.maximum(r.stderrs.mean(0), 1e-12))

# compressed psum inside shard_map
from repro.compat import shard_map
from repro.distributed.compression import compressed_psum

x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4) / 7.0


def f(xl):
    return compressed_psum(xl, "data")


got = shard_map(f, mesh=mesh, in_specs=P("data", None),
                out_specs=P("data", None))(x)
ref = np.tile(np.asarray(x).reshape(4, 2, 4).sum(0), (4, 1)).reshape(8, 4)
# int8 over shared scale: tolerance = scale
tol = float(np.abs(x).max()) / 127 * 4 + 1e-5
assert np.abs(np.asarray(got) - ref).max() <= tol
print("PROG_OK")

# fused-bucket kernels inside shard_map: the whole spec in one
# interpret-mode pallas_call per dim bucket, sharded functions x samples,
# matching the single-device fused path on the valid rows
from repro.core import gaussian_family
from repro.kernels import template as _template

fspec = MultiFunctionSpec.from_families(
    [harmonic_family(10, 4), harmonic_family(6, 2), gaussian_family(5, 4)])
_template.reset_launch_count()
zk = ZMCMultiFunctions(fspec, n_samples=32768, seed=5, mesh=mesh,
                       use_kernel=True)
rk = zk.evaluate(num_trials=1)
assert _template.launch_count() == 2, _template.launch_count()  # dims {2,4}
zs = ZMCMultiFunctions(fspec, n_samples=32768, seed=5, use_kernel=True)
rs = zs.evaluate(num_trials=1)
# same counters; only the psum association order differs from the
# single-device chain -> agreement at f32 rounding level, far below stderr
assert np.abs(rk.means - rs.means).max() < 1e-4, \
    np.abs(rk.means - rs.means).max()
print("PROG_OK_FUSED")

# exact sample split: n not divisible by the 4 data shards must still
# draw exactly n counters (the service cache folds consecutive windows,
# so a rounded-up shard range would overlap the next window)
from repro.core import rng as _rng
from repro.kernels.mc_eval import multi as _multi

_plan = _multi.plan_spec(MultiFunctionSpec.from_families(
    [harmonic_family(10, 3)]))
_key = _rng.fold_key(1, 0)
_n = 4098                              # per_shard=1025 -> 2 masked samples
_sh = _multi.sharded_eval_plan(_plan, _n, _key, mesh)
_ref = _multi.eval_plan(_plan, _n, _key)
assert float(_sh[0].n) == _n
assert np.allclose(np.asarray(_sh[0].s1), np.asarray(_ref[0].s1),
                   rtol=2e-6, atol=1e-4)
print("PROG_OK_EXACT_SPLIT")

# distributed ZMCNormal: strata over 'model', samples over 'data'
import jax.numpy as _jnp
from repro.core import ZMCNormal
f = lambda x: _jnp.sin(x[..., 0]) * _jnp.cos(x[..., 1])
zn = ZMCNormal(f, [[0, np.pi], [0, np.pi / 2]], seed=3, splits_per_dim=4,
               n_per_stratum=512, depth=4, k_split=8, mesh=mesh)
res = zn.evaluate(num_trials=2)
assert abs(res.integral - 2.0) < 0.02, res
print("PROG_OK_NORMAL")
