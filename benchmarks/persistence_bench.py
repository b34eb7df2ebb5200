"""Persistence benchmark: SIGKILL the engine mid-stream, restart, measure.

The durable store's promise is that process death costs *replay only*,
never recomputation: a request satisfied before the kill is served after
restart with zero kernel launches and a bit-identical result, and a
partially-met request tops up from its persisted ``sample_offset``
paying only for the missing rounds.  This benchmark proves both with a
real ``SIGKILL`` — no atexit hooks, no clean shutdown — and doubles as
the CI regression gate via ``--smoke``:

* **warm replay** — a child process serves the full request batch
  against a state dir and is SIGKILLed while still alive (the journal
  is its only legacy; the snapshot compactor never ran).  A second
  child replays the identical batch: asserts **0 launches** and a
  byte-identical result digest;

* **mid-stream kill** — a child is SIGKILLed after a single wave of a
  multi-round workload.  The restarted child finishes the job: asserts
  the digest matches an uninterrupted single-process reference run
  bit-for-bit, with strictly fewer launches than that reference (only
  the missing rounds are paid for).

The workload (``demo_workload``) includes infinite-domain Gaussian
requests, so the digest-equality assertions also pin the compactified
fused-kernel path across process death: an integral over R^d served
before the SIGKILL replays and tops up bit-identically, exactly like a
finite-box one.  It also includes parameter-sweep requests (two
overlapping 2-D grids): sweep cache streams are keyed per canonical
grid slice, so the same warm-replay / mid-kill-resume assertions prove
that a SIGKILLed sweep restarts from its persisted slice streams with
zero recomputation and bit-identical per-point results.

After each kill — before any restart can repair what it reads — the
parent runs the Layer-3 determinism auditor (``repro.analysis.streams``)
over the state dir and requires it clean: disjoint counter ranges,
gap-free deposit rounds, a single round quantum, no orphans.  A torn
tail record is expected post-SIGKILL and is reported, not flagged.

``--json-out`` writes the measurements (including the audit summaries)
as ``BENCH_persistence.json`` so CI can archive the perf trajectory per
commit.

The parent never starts a JAX backend: it spawns children and reads
their state dirs with the auditor, which computes nothing on a device.
Only the children hold a device.  They run on the CPU backend
(``JAX_PLATFORMS=cpu``) unless the caller sets ``JAX_PLATFORMS``
itself, since a chip admits one process at a time and each child must
then release it before the next starts.  On CPU the
kernels run interpreted and only launch counts + digests are meaningful.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


# -- child: one engine process against a state dir ---------------------------

def child_main(args) -> int:
    import numpy as np  # noqa: F401  (jax import below pulls it anyway)

    from repro.kernels import template
    from repro.launch.serve_integrals import demo_workload
    from repro.service import IntegrationEngine

    engine = IntegrationEngine(
        seed=args.seed, round_samples=args.round_samples,
        max_rounds_per_wave=args.max_rounds_per_wave,
        state_dir=args.state_dir, compact_on_start=args.compact_on_start)
    reqs = demo_workload(args.requests, n_fn=args.n_fn,
                         n_samples=args.samples, sweeps=args.sweeps)

    template.reset_launch_count()
    t0 = time.time()
    tickets = [engine.submit(r) for r in reqs]

    if args.waves >= 0:
        # serve exactly N waves, then hang so the parent can SIGKILL us
        # mid-stream — the pending requests stay partially met
        for _ in range(args.waves):
            engine.step()
        print("KILLME", flush=True)
        time.sleep(600)
        return 1     # unreachable when the parent does its job

    while engine.step():
        pass
    dt = time.time() - t0
    results = [engine.poll(t) for t in tickets]
    assert all(r is not None for r in results), "unserved requests"

    digest = hashlib.sha256()
    for res in results:
        digest.update(res.means.astype("<f4").tobytes())
        digest.update(res.stderrs.astype("<f4").tobytes())
    print("DIGEST " + json.dumps({
        "digest": digest.hexdigest(),
        "launches": template.launch_count(),
        "served": len(results),
        "from_cache": sum(r.served_from_cache for r in results),
        "seconds": round(dt, 3),
    }), flush=True)

    if args.linger:
        # stay alive *without* shutting down: the parent's SIGKILL models
        # a crash where snapshot-on-shutdown never ran (journal-only)
        print("KILLME", flush=True)
        time.sleep(600)
        return 1
    engine.close()
    return 0


# -- parent: orchestrate children, deliver SIGKILLs ---------------------------

def _audit(state_dir: str, label: str) -> dict:
    """Run the Layer-3 determinism auditor (read-only) over a state dir.

    Called on the exact bytes a SIGKILL left behind — before any restart
    touches them — so a violation here means the WAL protocol itself is
    broken, not that recovery papered over it.  A torn tail record is
    expected after a kill and is reported, not flagged.
    """
    if _SRC not in sys.path:
        sys.path.insert(0, _SRC)
    from repro.analysis.streams import audit_state_dir
    from repro.analysis.violations import render

    report = audit_state_dir(state_dir)
    if report.violations:
        print(render(report.violations))
    assert report.ok, f"{label}: state dir failed the determinism audit"
    print(f"audit {label}: {report.summary()}")
    return {"ok": True, "streams": report.streams,
            "journal_records": report.journal_records,
            "deposits_folded": report.deposits_folded,
            "deposits_replayed": report.deposits_replayed,
            "truncated_tail_bytes": report.truncated_tail_bytes}


def _child_platforms() -> str:
    """The children's JAX backend: the caller's ``JAX_PLATFORMS``, else
    the CPU."""
    return os.environ.get("JAX_PLATFORMS") or "cpu"


def _run_child(state_dir: str, cfg, *, waves: int = -1, linger: bool = False,
               compact_on_start: bool = False) -> dict | None:
    """Run one engine process; SIGKILL it when it prints KILLME.

    Returns the child's DIGEST payload, or None for a mid-stream kill
    (no digest was reached).
    """
    env = os.environ.copy()
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = _child_platforms()
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--state-dir", state_dir,
           "--requests", str(cfg.requests), "--n-fn", str(cfg.n_fn),
           "--samples", str(cfg.samples),
           "--round-samples", str(cfg.round_samples),
           "--max-rounds-per-wave", str(cfg.max_rounds_per_wave),
           "--seed", str(cfg.seed), "--waves", str(waves),
           "--sweeps", str(cfg.sweeps)]
    if linger:
        cmd.append("--linger")
    if compact_on_start:
        cmd.append("--compact-on-start")

    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    digest = None
    killed = False
    try:
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("DIGEST "):
                digest = json.loads(line[len("DIGEST "):])
            elif line == "KILLME":
                os.kill(proc.pid, signal.SIGKILL)
                killed = True
                break
    finally:
        proc.stdout.close()
        proc.wait()
    if not killed and proc.returncode != 0:
        raise RuntimeError(f"child exited with {proc.returncode}")
    if not killed and (waves >= 0 or linger):
        raise RuntimeError("child was supposed to be killed but exited")
    return digest


def run(cfg) -> int:
    print(f"# {cfg.requests} requests, budget {cfg.samples} samples in "
          f"rounds of {cfg.round_samples} "
          f"({cfg.samples // cfg.round_samples} rounds/stream); children "
          f"run with JAX_PLATFORMS={_child_platforms()}")
    report: dict = {"bench": "persistence", "requests": cfg.requests,
                    "samples": cfg.samples,
                    "round_samples": cfg.round_samples, "phases": {}}

    with tempfile.TemporaryDirectory(prefix="zmc-persist-") as root:
        # -- phase 1: cold serve, then SIGKILL before any clean shutdown
        state_a = os.path.join(root, "warm")
        cold = _run_child(state_a, cfg, linger=True)
        print(f"cold:         {cold['launches']} launches, "
              f"{cold['seconds']}s  (then SIGKILLed, journal-only state)")
        audits = {"journal_only_post_sigkill":
                  _audit(state_a, "journal-only post-SIGKILL")}

        # -- phase 2: restart against the journal -> zero launches
        warm = _run_child(state_a, cfg)
        print(f"warm restart: {warm['launches']} launches, "
              f"{warm['from_cache']}/{warm['served']} pure cache hits, "
              f"{warm['seconds']}s")
        assert warm["launches"] == 0, \
            f"warm replay launched kernels: {warm['launches']}"
        assert warm["from_cache"] == warm["served"], warm
        assert warm["digest"] == cold["digest"], \
            "restarted results differ from the pre-kill results"

        # -- phase 3: SIGKILL mid-stream (after one wave of a
        # multi-round budget), restart, finish -> only delta rounds paid
        state_b = os.path.join(root, "midkill")
        _run_child(state_b, cfg, waves=1)
        audits["midwave_post_sigkill"] = \
            _audit(state_b, "mid-wave post-SIGKILL")
        resumed = _run_child(state_b, cfg)
        state_c = os.path.join(root, "reference")
        reference = _run_child(state_c, cfg)
        print(f"mid-kill resume: {resumed['launches']} launches vs "
              f"{reference['launches']} uninterrupted, "
              f"{resumed['seconds']}s vs {reference['seconds']}s")
        assert resumed["digest"] == reference["digest"], \
            "resumed stream is not bit-identical to the uninterrupted run"
        assert 0 < resumed["launches"] < reference["launches"], \
            (resumed["launches"], reference["launches"])

        audits["midkill_post_resume"] = _audit(state_b, "post-resume")
        report["phases"] = {"cold": cold, "warm_restart": warm,
                            "midkill_resume": resumed,
                            "uninterrupted_reference": reference}
        report["audits"] = audits
        saved = reference["launches"] - resumed["launches"]
        print(f"-> SIGKILL cost zero recomputation: warm replay 0 launches; "
              f"mid-stream kill saved {saved} of {reference['launches']} "
              f"launches on resume")

    if cfg.json_out:
        with open(cfg.json_out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {cfg.json_out}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true",
                    help="internal: run one engine process")
    ap.add_argument("--state-dir", default=None)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--n-fn", type=int, default=8)
    ap.add_argument("--samples", type=int, default=3 * 8192)
    ap.add_argument("--round-samples", type=int, default=8192)
    ap.add_argument("--max-rounds-per-wave", type=int, default=1,
                    help="1 -> one round per stream per wave, so a kill "
                         "after wave k leaves streams k rounds deep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweeps", type=int, default=2,
                    help="append N overlapping parameter-sweep requests "
                         "to the workload (sweep slice streams must "
                         "survive SIGKILL like any other)")
    ap.add_argument("--waves", type=int, default=-1,
                    help="child: serve N waves then await SIGKILL (-1: all)")
    ap.add_argument("--linger", action="store_true",
                    help="child: after serving, await SIGKILL instead of "
                         "shutting down cleanly")
    ap.add_argument("--compact-on-start", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run with the same assertions")
    ap.add_argument("--json-out", default=None,
                    help="write measurements as JSON (BENCH_*.json)")
    args = ap.parse_args()

    if args.child:
        if not args.state_dir:
            ap.error("--child requires --state-dir")
        return child_main(args)
    if args.smoke:
        args.requests, args.n_fn = 12, 4
        args.round_samples, args.samples = 4096, 3 * 4096
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
