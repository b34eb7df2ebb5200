import io
import json
import os
import sys

import numpy as np
import pytest

# the benchmark is the package ``bench`` at the root of the checkout
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Small sizes at which the harness runs on the CPU with interpreted
# kernels.  The chi^2 limits are for the integrands a run checks here: some
# 1,500 of the paper's (noise sqrt(2/1500) = 0.04) and some 250 adaptive
# ones, each stopped after a round or two of 2048 samples, whose stderr is
# itself noisy; the faults read 0.5 (paper) and far more (VEGAS).
TINY = {
    "paper_harmonic_d4.closed2": {
        "config": {"engine": {"round_samples": 2048},
                   "request": {"n_fn": 16, "n_samples": 4096},
                   "check": {"chi2_excess": 0.2}},
        "traffic": {"clients": 2, "warmup_per_client": 1}},
    "genz_corner_vegas_d3.closed4": {
        "config": {"engine": {"round_samples": 2048,
                              "max_rounds_per_wave": 2},
                   "request": {"n_fn": 16, "target_stderr": 5e-4},
                   "check": {"chi2_excess": 0.5}},
        "traffic": {"clients": 2, "warmup_per_client": 1}},
}


@pytest.fixture
def run_cell():
    """Run a cell at its tiny size; returns (exit code, result line or
    None, standard error)."""
    from bench import harness

    def run(workload, seed=12345, trace=False, seconds=1.0, late_s=10.0,
            overrides=None):
        out, err = io.StringIO(), io.StringIO()
        ovr = harness._merge(TINY[workload], overrides)
        rc = harness.run(workload, seed, seconds, trace, require_tpu=False,
                         overrides=ovr, late_s=late_s, out=out, err=err)
        lines = out.getvalue().strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()

    return run


def plant(monkeypatch, fault):
    """Break the timed path from the window's start on: ``fault(sums)``
    rewrites every wave's sums just before the cache folds them.  With
    ``fault == "unchanged"`` the cache folds nothing (a step that returns
    its state unchanged); with ``"unjournaled"`` the store drops every
    wave's deposits instead of journaling them; with ``"unsynced"`` it
    writes them without an fsync.  Set-up stays sound, so only the window
    can fail."""
    from bench import harness
    from repro.core.direct_mc import SumsState
    from repro.service.batcher import RoundBatcher
    from repro.service.cache import ResultCache
    from repro.service.store import DurableStore

    orig_loop = harness.loadgen.closed_loop
    orig_deposit = RoundBatcher.deposit
    orig_write = DurableStore._write

    def install():
        if fault == "unchanged":
            monkeypatch.setattr(ResultCache, "deposit_wave",
                                lambda self, deposits: None)
            return
        if fault == "unjournaled":
            monkeypatch.setattr(DurableStore, "append_deposits",
                                lambda self, payloads: None)
            return
        if fault == "unsynced":
            def write(self, record):
                self.fsync = False
                return orig_write(self, record)
            monkeypatch.setattr(DurableStore, "_write", write)
            return

        def deposit(self, wave):
            wave.results = [
                (entry, r, fault(SumsState(
                    s1=np.asarray(s.s1, np.float32).copy(),
                    s2=np.asarray(s.s2, np.float32).copy(),
                    n=np.asarray(s.n))))
                for entry, r, s in wave.results]
            return orig_deposit(self, wave)

        monkeypatch.setattr(RoundBatcher, "deposit", deposit)

    def closed_loop(serve, draw, mix, seconds, *, on_window_start, **kw):
        def start():
            on_window_start()
            install()
        return orig_loop(serve, draw, mix, seconds, on_window_start=start,
                         **kw)

    monkeypatch.setattr(harness.loadgen, "closed_loop", closed_loop)


def half_left_out(s):
    """Half of the batch's integrands left out: their sums never made."""
    h = len(s.s1) // 2
    s.s1[h:] = 0.0
    s.s2[h:] = 0.0
    return s


def samples_halved(s):
    """Half of the samples left out, the mean taken over the rest."""
    return s._replace(s1=s.s1 / 2, s2=s.s2 / 2, n=s.n / 2)


def answer_altered(s):
    """One answer altered where it is produced: the first integrand's sum
    moved by one unit per sample."""
    s.s1[0] += float(np.asarray(s.n).reshape(-1)[0])
    return s

