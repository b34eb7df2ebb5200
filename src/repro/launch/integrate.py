"""Integration launcher: the paper's workload as a production job.

``python -m repro.launch.integrate`` evaluates a multi-function spec with
checkpointed rounds, the straggler watchdog and restart-on-failure — the
fault-tolerant driver that a cluster deployment would run per pod, with the
mesh handling intra-pod distribution (functions x model, samples x data).
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.core import (MultiFunctionSpec, ZMCMultiFunctions,
                        harmonic_analytic, harmonic_family)
from repro.distributed.fault_tolerance import StepWatchdog, run_with_restarts


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("REPRO_MULTIHOST"):
        from repro.launch.multihost import initialize_if_needed
        initialize_if_needed()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-functions", type=int, default=100)
    ap.add_argument("--dim", type=int, default=4)
    ap.add_argument("--samples", type=int, default=10**6)
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas fused sampler (interpret mode off-TPU)")
    ap.add_argument("--mesh", action="store_true",
                    help="shard over all local devices")
    args = ap.parse_args()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh_for
        import jax
        n = len(jax.devices())
        mp = 2 if n % 2 == 0 and n > 1 else 1
        mesh = make_mesh_for(model_parallel=mp)

    spec = MultiFunctionSpec.from_families(
        [harmonic_family(args.n_functions, args.dim)])
    zmc = ZMCMultiFunctions(spec, n_samples=args.samples, seed=args.seed,
                            mesh=mesh, use_kernel=args.use_kernel)

    watchdog = StepWatchdog()

    def body(attempt: int):
        means, stds = [], []
        for t in range(args.trials):
            with watchdog:
                r = zmc.evaluate_resumable(rounds=args.rounds,
                                           checkpoint_dir=args.ckpt_dir,
                                           trial=t)
            means.append(r.means[0])
            stds.append(r.stderrs[0])
        return np.stack(means), np.stack(stds)

    t0 = time.time()
    means, stds = run_with_restarts(body, max_restarts=2)
    dt = time.time() - t0

    exact = harmonic_analytic(args.n_functions, args.dim)
    fbar = means.mean(0)
    dfn = means.std(0, ddof=1) if args.trials > 1 else stds.mean(0)
    within = np.abs(fbar - exact) <= 2 * np.maximum(dfn, 1e-12)
    print(f"{args.n_functions} integrands x {args.samples:.0e} samples "
          f"x {args.trials} trials in {dt:.1f}s "
          f"({dt / max(args.trials, 1):.1f}s per trial)")
    print(f"|F_bar - exact| <= 2*dF for {within.sum()}/{len(within)} "
          f"integrands; stragglers: {watchdog.straggler_count}")
    worst = np.argmax(np.abs(fbar - exact) / np.maximum(dfn, 1e-12))
    print(f"worst pull at n={worst + 1}: est {fbar[worst]:+.5f} "
          f"exact {exact[worst]:+.5f} (dF {dfn[worst]:.2e})")


if __name__ == "__main__":
    main()
