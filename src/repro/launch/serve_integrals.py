"""Integration-as-a-service launcher.

``python -m repro.launch.serve_integrals --requests 64`` stands up the
continuously-batching :class:`~repro.service.engine.IntegrationEngine`,
feeds it a mixed-dimension grid-scan workload (the ZMCintegral-v5 usage
pattern: many clients asking for related parameter sweeps), and reports
throughput, launch counts and cache behavior.  ``--thread`` exercises
the async submit/poll worker; the default drives waves synchronously.

This is the service-layer sibling of ``repro.launch.integrate`` (the
one-shot fault-tolerant job): same kernels, same counters, but requests
arrive over time, dedupe against each other and top up cached streams.

**Wave pipeline**: each wave fuses its rounds into multi-round kernels —
an R-round refinement over B dimension buckets costs B launches instead
of R x B — and with ``--thread`` the worker double-buffers waves
(wave k+1 dispatches while wave k's results transfer, deposit and
group-commit to the WAL; ``--no-pipeline`` serializes them).
``--max-rounds-per-wave`` caps rounds per stream per wave (the fused
kernel's R); ``--max-items-per-wave`` bounds the whole wave, with the
budget assigned round-robin across requests so heavy precision asks
cannot starve small latency-sensitive ones.

**Warm starts**: pass ``--state-dir PATH`` and the engine journals every
round deposit to disk (crash-safe, checksummed) and snapshots on clean
shutdown.  Re-launching against the same state dir — even after a
SIGKILL — resumes every cached stream at its exact ``sample_offset``:
requests the previous process already satisfied are served with zero
kernel launches, partially-met ones only pay for the missing rounds, and
all results are bit-identical to an uninterrupted run.  ``--state-dir``
pins the seed and round size (stored in ``meta.json``); reopening with
different values is refused.  ``--compact-on-start`` folds the replayed
journal into one npz snapshot before serving:

    python -m repro.launch.serve_integrals --requests 64 --state-dir /tmp/zmc
    # ... kill -9 it, then:
    python -m repro.launch.serve_integrals --requests 64 --state-dir /tmp/zmc \\
        --compact-on-start      # -> 64 pure cache hits, 0 launches

**Telemetry** (:mod:`repro.obs`): ``--trace-out trace.json`` records a
span per wave-pipeline stage (plan / launch / device_execute / transfer
/ deposit / wal_commit) in Chrome-trace format — open the file directly
in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.
``--jax-trace`` additionally wraps spans in ``jax.profiler``
annotations so they land in XLA profiler timelines.  ``--metrics-port
P`` serves Prometheus text at ``http://127.0.0.1:P/metrics`` (plus
``/metrics.json`` and ``/convergence``) while the workload runs;
``--metrics-json PATH`` writes a final metrics + convergence snapshot
on exit.  Any telemetry flag also turns on per-stream convergence
accounting: the run reports each stream's stderr-vs-rounds trajectory,
queryable afterwards via ``engine.stderr_trajectory(stream_id)``.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.obs import clock as _clock

from repro.core import abs_sum_family, gaussian_family, harmonic_family
from repro.core import genz
from repro.service.api import IntegrationRequest, SweepRequest


def demo_workload(n_requests: int, *, n_fn: int = 8,
                  n_samples: int | None = 16384,
                  target_stderr: float | None = None,
                  duplicate_every: int = 4,
                  sweeps: int = 0) -> list:
    """A mixed-dimension request stream with deliberate overlap.

    Cycles through the registered forms at dims 2-4 (so batching has
    buckets to fuse) and re-issues every ``duplicate_every``-th request
    verbatim, modeling distinct clients scanning overlapping grids — the
    canonicalizer must dedupe those into shared cache entries.  The mix
    includes infinite-domain Gaussians (over R^d and the positive
    orthant): compactified families ride the same fused buckets, cache
    streams and persistence digests as finite ones.

    With ``sweeps=k``, appends ``k`` sweep requests
    (:class:`SweepRequest`) — each a
    harmonic template scanned over a deterministic 2-D (a, b) grid, the
    grids overlapping pairwise along the slowest axis — so persistence
    and restart drills cover sweep cache streams too (``SweepResult``
    exposes the same ``means``/``served_from_cache`` surface the drills
    digest).
    """
    reqs: list = []
    makers = [
        lambda i: harmonic_family(n_fn, 2 + i % 3),
        lambda i: abs_sum_family(n_fn, 2 + i % 3,
                                 np.linspace(0.5, 2.0, n_fn), ),
        lambda i: gaussian_family(n_fn, 2 + i % 3),
        lambda i: genz.oscillatory(n_fn, 2 + i % 3, seed=i % 5)[0],
        lambda i: genz.corner_peak(n_fn, 2 + i % 3, seed=i % 5)[0],
        lambda i: gaussian_family(n_fn, 2 + i % 3, lo=-np.inf, hi=np.inf),
        lambda i: gaussian_family(n_fn, 2 + i % 3, lo=0.0, hi=np.inf),
    ]
    for i in range(n_requests):
        if duplicate_every and i % duplicate_every == duplicate_every - 1:
            # verbatim re-ask of an earlier request (different client)
            src = reqs[i // 2]
            fams = src.families
        else:
            fams = (makers[i % len(makers)](i),)
        reqs.append(IntegrationRequest.make(
            fams, n_samples=n_samples, target_stderr=target_stderr))
    for j in range(sweeps):
        # consecutive sweeps extend the slowest-varying axis, so their
        # canonical slice prefixes align and dedupe at the cache
        grid = {"a": np.linspace(0.5, 2.0, 4 + 2 * j),
                "b": np.linspace(-1.0, 1.0, 8)}
        reqs.append(SweepRequest.make(
            harmonic_family(1, 2 + j % 3), grid,
            n_samples=n_samples, target_stderr=target_stderr))
    return reqs


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--n-fn", type=int, default=8,
                    help="functions per requested family")
    ap.add_argument("--samples", type=int, default=16384)
    ap.add_argument("--target-stderr", type=float, default=None,
                    help="serve to precision instead of a fixed budget")
    ap.add_argument("--round-samples", type=int, default=8192)
    ap.add_argument("--max-rounds-per-wave", type=int, default=8,
                    help="rounds per stream per wave — the R of each "
                         "fused multi-round launch")
    ap.add_argument("--max-items-per-wave", type=int, default=None,
                    help="total round budget per wave, assigned "
                         "round-robin across pending requests (fairness "
                         "under load); default unbounded")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="serialize waves instead of double-buffering "
                         "dispatch against host deposits (--thread mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-kernel", action="store_true",
                    help="chunked JAX path instead of fused Pallas")
    ap.add_argument("--mesh", action="store_true",
                    help="shard over all local devices")
    ap.add_argument("--thread", action="store_true",
                    help="run the async worker thread (submit/poll mode)")
    ap.add_argument("--state-dir", default=None,
                    help="persist the cache here (journal + snapshots); "
                         "re-launching against it warm-starts every stream")
    ap.add_argument("--compact-on-start", action="store_true",
                    help="fold the replayed journal into one npz snapshot "
                         "before serving")
    ap.add_argument("--audit-state", action="store_true",
                    help="audit --state-dir against the determinism "
                         "invariants (repro.analysis Layer 3) and exit; "
                         "serves nothing")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto span timeline of "
                         "every wave-pipeline stage here")
    ap.add_argument("--jax-trace", action="store_true",
                    help="wrap pipeline spans in jax.profiler annotations "
                         "(visible in XLA profiler timelines)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus metrics on this port while the "
                         "workload runs (/metrics, /metrics.json, "
                         "/convergence); 0 picks a free port")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write a final metrics + convergence snapshot "
                         "here on exit")
    args = ap.parse_args()

    if args.audit_state:
        if not args.state_dir:
            ap.error("--audit-state requires --state-dir")
        from repro.analysis import render
        from repro.analysis.streams import audit_state_dir
        report = audit_state_dir(args.state_dir)
        if report.violations:
            print(render(report.violations))
        print(report.summary())
        raise SystemExit(0 if report.ok else 1)

    from repro.kernels import template
    from repro.service import IntegrationEngine

    mesh = None
    if args.mesh:
        import jax
        from repro.launch.mesh import make_mesh_for
        n = len(jax.devices())
        mp = 2 if n % 2 == 0 and n > 1 else 1
        mesh = make_mesh_for(model_parallel=mp)

    telemetry = (args.trace_out is not None or args.jax_trace
                 or args.metrics_port is not None
                 or args.metrics_json is not None)
    obs = None
    metrics_server = None
    if telemetry:
        from repro.obs import Observability
        obs = Observability.enabled(trace_path=args.trace_out,
                                    jax_annotations=args.jax_trace)
        if args.metrics_port is not None:
            from repro.obs.export import MetricsServer
            metrics_server = MetricsServer(obs.metrics,
                                           port=args.metrics_port,
                                           convergence=obs.convergence)
            print(f"metrics: http://127.0.0.1:{metrics_server.port}/metrics")

    engine = IntegrationEngine(
        seed=args.seed, round_samples=args.round_samples,
        use_kernel=not args.no_kernel, mesh=mesh,
        max_rounds_per_wave=args.max_rounds_per_wave,
        max_items_per_wave=args.max_items_per_wave,
        pipeline_waves=not args.no_pipeline,
        state_dir=args.state_dir, compact_on_start=args.compact_on_start,
        obs=obs)
    if engine.cache.recovered is not None:
        rec = engine.cache.recovered
        print(f"warm start: {len(rec.entries)} persisted streams "
              f"({rec.journal_records} journal records replayed, "
              f"{rec.truncated_bytes} corrupt tail bytes truncated)")
    reqs = demo_workload(
        args.requests, n_fn=args.n_fn,
        n_samples=None if args.target_stderr else args.samples,
        target_stderr=args.target_stderr)

    template.reset_launch_count()
    t0 = _clock.monotonic()
    if args.thread:
        engine.start()
        tickets = [engine.submit(r) for r in reqs]
        results = [engine.result(t, timeout=600.0) for t in tickets]
        engine.stop()
    else:
        tickets = [engine.submit(r) for r in reqs]
        while engine.step():
            pass
        results = [engine.poll(t) for t in tickets]
    dt = _clock.monotonic() - t0
    launches = template.launch_count()

    n_fn_total = sum(r.n_fn_total for r in results)
    hits = sum(r.served_from_cache for r in results)
    print(f"served {len(results)} requests ({n_fn_total} integrands) "
          f"in {dt:.1f}s -> {len(results) / dt:.1f} req/s, "
          f"{launches} kernel launches "
          f"({engine.batcher.fallback_rounds} chunked fallback rounds), "
          f"{hits} pure cache hits")
    print(f"engine: {engine.stats}")
    print(f"cache:  {engine.cache.stats()}")
    print(f"stragglers: {engine.watchdog.straggler_count}")
    worst = max(float(r.stderrs.max()) for r in results)
    print(f"worst stderr served: {worst:.3e}")
    engine.close()   # snapshot-on-shutdown when --state-dir is set
    if args.state_dir:
        print(f"state snapshotted to {args.state_dir} "
              f"(journal compacted to {engine.store.journal_size()} bytes)")

    if obs is not None:
        streams = obs.convergence.streams()
        if streams:
            print(f"convergence: {len(streams)} streams tracked; "
                  "final stderr per stream:")
            for sid in streams:
                traj = obs.convergence.trajectory(sid)
                last = traj[-1]
                print(f"  {sid[:16]}  rounds={last.rounds_done:4d} "
                      f"n={last.n:9d}  stderr_max={last.stderr_max:.3e}")
        if args.metrics_json:
            from repro.obs.export import write_snapshot
            write_snapshot(args.metrics_json, obs.metrics,
                           convergence=obs.convergence)
            print(f"metrics snapshot written to {args.metrics_json}")
        if metrics_server is not None:
            metrics_server.close()
        obs.close()
        if args.trace_out:
            print(f"trace written to {args.trace_out} "
                  "(open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
