"""Telemetry through the engine: traces cover the pipeline, metrics agree
with engine observables, failure paths emit attributable events, and
every completed request exposes a stderr-vs-rounds trajectory.

These are the service-level counterparts of ``tests/obs``: the obs tests
exercise the primitives in isolation; here the assertion is that the
*wiring* through plan/launch/deposit is complete and honest.
"""

import numpy as np
import pytest

from repro.core import gaussian_family, harmonic_family
from repro.distributed.fault_tolerance import StepWatchdog
from repro.kernels import template
from repro.obs import Observability
from repro.obs.trace import STAGES
from repro.service import IntegrationClient

R = 4096


@pytest.fixture
def events():
    return []


@pytest.fixture
def obs(events):
    """A live Observability bundle whose trace feeds a plain list."""
    o = Observability.enabled(sinks=(events.append,))
    yield o
    o.close()


def _instants(events, name):
    return [e for e in events if e.get("ph") == "i" and e["name"] == name]


class TestTraceCoverage:
    def test_sync_wave_covers_all_six_stages(self, make_engine, obs, events,
                                             tmp_path):
        engine = make_engine(state_dir=str(tmp_path), obs=obs)
        IntegrationClient(engine).integrate(
            [harmonic_family(3, 2), gaussian_family(2, 2)], n_samples=2 * R)
        spans = {e["name"] for e in events if e.get("ph") == "X"}
        assert spans.issuperset(STAGES)

    def test_wal_commit_absent_without_durable_store(self, make_engine, obs,
                                                     events):
        engine = make_engine(obs=obs)
        IntegrationClient(engine).integrate([harmonic_family(3, 2)],
                                            n_samples=R)
        spans = {e["name"] for e in events if e.get("ph") == "X"}
        assert "wal_commit" not in spans
        assert spans.issuperset(set(STAGES) - {"wal_commit"})


class TestMetricAgreement:
    def test_counters_match_engine_observables(self, make_engine, obs):
        template.reset_launch_count()
        engine = make_engine(obs=obs)
        client = IntegrationClient(engine)
        client.integrate([harmonic_family(3, 2)], n_samples=3 * R)
        client.integrate([gaussian_family(2, 2), harmonic_family(2, 2)],
                         n_samples=2 * R)
        m = obs.m
        assert m["launches"].value() == template.launch_count()
        assert m["fallback_rounds"].value() == engine.batcher.fallback_rounds
        assert m["waves"].value() == engine.stats.waves
        assert m["served"].value() == engine.stats.served == 2
        assert m["submitted"].value() == engine.stats.submitted == 2

    def test_warm_replay_counts_cache_hit_and_zero_launch(self, make_engine,
                                                          obs):
        engine = make_engine(obs=obs)
        client = IntegrationClient(engine)
        fam = [harmonic_family(3, 2)]
        client.integrate(fam, n_samples=2 * R)
        waves_before = engine.stats.waves
        client.integrate(fam, n_samples=2 * R)       # identical → cache
        assert engine.stats.waves == waves_before
        assert obs.m["cache_requests"].value(outcome="hit") >= 1
        assert obs.m["warm_zero_launch"].value() == 1
        assert obs.m["served"].value() == 2

    def test_gauges_drain_to_zero_at_quiescence(self, make_engine, obs):
        engine = make_engine(obs=obs)
        IntegrationClient(engine).integrate([harmonic_family(3, 2)],
                                            n_samples=2 * R)
        assert obs.m["pending"].value() == 0
        assert obs.m["inflight"].value() == 0


class TestConvergenceAccounting:
    def test_every_result_stream_has_a_trajectory(self, make_engine, obs):
        engine = make_engine(obs=obs)
        res = IntegrationClient(engine).integrate(
            [harmonic_family(3, 2), gaussian_family(2, 2)], n_samples=4 * R)
        assert len(res.stream_ids) == 2
        for sid in res.stream_ids:
            traj = engine.stderr_trajectory(sid)
            assert traj, sid
            rounds = [p.rounds_done for p in traj]
            assert rounds == sorted(rounds)
            assert traj[-1].rounds_done == 4        # full budget deposited
            assert traj[-1].stderr_max > 0

    def test_stderr_decreases_with_rounds(self, make_engine, obs):
        engine = make_engine(obs=obs)
        res = IntegrationClient(engine).integrate([harmonic_family(3, 2)],
                                                  n_samples=8 * R)
        (sid,) = res.stream_ids
        traj = engine.stderr_trajectory(sid)
        assert len(traj) >= 2
        assert traj[-1].stderr_max < traj[0].stderr_max

    def test_disabled_obs_keeps_api_shape(self, make_engine):
        engine = make_engine()                       # Observability.disabled()
        res = IntegrationClient(engine).integrate([harmonic_family(3, 2)],
                                                  n_samples=R)
        assert len(res.stream_ids) == 1
        assert engine.stderr_trajectory(res.stream_ids[0]) == []


class TestFailurePathEvents:
    def test_torn_deposit_emits_restart_event_with_identity(
            self, make_engine, obs, events, tmp_path):
        engine = make_engine(state_dir=str(tmp_path), max_rounds_per_wave=8,
                             obs=obs)
        store = engine.store
        orig = store.append_deposits
        fails = {"left": 1}

        def flaky(payloads):
            payloads = list(payloads)
            if fails["left"]:
                fails["left"] -= 1
                orig(payloads[:1])
                raise OSError("injected torn group commit")
            return orig(payloads)

        store.append_deposits = flaky
        res = IntegrationClient(engine).integrate([harmonic_family(4, 3)],
                                                  n_samples=3 * R)
        assert engine.stats.restarts == 1
        (ev,) = _instants(events, "wave_restart")
        assert ev["args"]["error"] == "OSError"
        assert ev["args"]["attempt"] == 0
        # the event names the streams the replayed wave was computing
        assert res.stream_ids[0][:16] in ev["args"]["streams"]
        assert obs.m["restarts"].value() == 1

    def test_pipelined_deposit_retry_event(self, make_engine, obs, events,
                                           tmp_path):
        engine = make_engine(state_dir=str(tmp_path), max_rounds_per_wave=8,
                             obs=obs)
        store = engine.store
        orig = store.append_deposits
        fails = {"left": 1}

        def flaky(payloads):
            if fails["left"]:
                fails["left"] -= 1
                raise OSError("injected commit failure")
            return orig(payloads)

        store.append_deposits = flaky
        engine.start()
        res = IntegrationClient(engine).integrate([harmonic_family(4, 3)],
                                                  n_samples=3 * R)
        engine.stop()
        retries = _instants(events, "deposit_retry")
        assert retries, [e["name"] for e in events if e.get("ph") == "i"]
        assert retries[0]["args"]["error"] == "OSError"
        assert res.stream_ids[0][:16] in retries[0]["args"]["streams"]
        assert obs.m["restarts"].value() >= 1

    def test_straggler_event_carries_wave_and_stream(self, make_engine, obs,
                                                     events):
        # a watchdog pre-seeded with an instant history makes the very
        # first (real, nonzero-duration) wave a straggler
        dog = StepWatchdog(threshold=0.0, warmup=1)
        dog.durations.append(0.0)
        engine = make_engine(obs=obs, watchdog=dog)
        res = IntegrationClient(engine).integrate([harmonic_family(3, 2)],
                                                  n_samples=R)
        assert dog.straggler_count >= 1
        evs = _instants(events, "straggler")
        assert len(evs) == dog.straggler_count
        assert evs[0]["args"]["duration"] > 0
        assert res.stream_ids[0][:16] in evs[0]["args"]["streams"]
        assert obs.m["stragglers"].value() == dog.straggler_count


# spans the request path and the worker's loop add beside the stages
NEW_SPANS = ("submit", "lock_wait", "complete", "idle", "request")


def _spans(events, name=None):
    return [e for e in events if e.get("ph") == "X"
            and (name is None or e["name"] == name)]


@pytest.fixture
def served_adaptive(make_engine, obs, events, tmp_path):
    """One adaptive request served by the pipelined worker thread, with a
    durable store: (engine, result, the trace's spans)."""
    engine = make_engine(state_dir=str(tmp_path), obs=obs,
                         adapt_rounds_per_epoch=1, adapt_max_epochs=3,
                         adapt_pilot_samples=1024)
    engine.start()
    res = IntegrationClient(engine).integrate(
        [gaussian_family(2, 2, sigma=np.asarray([0.15, 0.25]))],
        target_stderr=5e-4, adaptive=True)
    engine.stop()
    return engine, res, _spans(events)


class TestRequestPathTracing:
    def test_adaptive_request_yields_submit_lock_complete_request(
            self, served_adaptive, obs):
        engine, res, spans = served_adaptive
        names = {s["name"] for s in spans}
        assert {"submit", "lock_wait", "complete", "request"} <= names
        (submit,) = _spans(spans, "submit")
        assert submit["args"]["ticket"] == res.ticket
        assert submit["args"]["cache"] == "miss"
        assert submit["args"]["n_fn"] == 2
        parts = {p[0] for p in submit["args"]["parts"]}
        assert {"pilot", "lock_wait"} <= parts
        (req,) = _spans(spans, "request")
        assert req["tid"] == submit["tid"]
        assert req["ts"] == submit["ts"]
        assert req["args"]["ticket"] == res.ticket
        assert req["args"]["waves"] >= 1
        assert req["args"]["rounds"] >= req["args"]["waves"]
        assert req["args"]["queue_us"] >= 0
        assert obs.m["grid_pilots"].value() >= \
            1 + obs.m["grid_refits"].value()
        if obs.m["grid_refits"].value():
            refits = [p for s in _spans(spans, "plan")
                      for p in s["args"].get("parts", ())
                      if p[0] == "refit"]
            assert refits

    def test_every_wave_span_carries_its_wave(self, served_adaptive):
        _, _, spans = served_adaptive
        worker = _spans(spans, "launch")[0]["tid"]
        on_worker = [s for s in spans if s["tid"] == worker]
        assert {s["name"] for s in on_worker} >= {"plan", "launch",
                                                   "lock_wait", "complete"}
        for s in on_worker:
            assert isinstance(s["args"].get("wave"), int), \
                (s["name"], s["args"])
        launches = {s["args"]["wave"] for s in _spans(spans, "launch")}
        for name in ("device_execute", "transfer", "deposit"):
            assert {s["args"]["wave"] for s in _spans(spans, name)} \
                <= launches
        plans = {s["args"]["wave"]: s for s in _spans(spans, "plan")}
        for w in launches:
            assert plans[w]["args"]["tickets"]
        # journal writes outside a wave are the submit's grid and alloc
        submits = _spans(spans, "submit")
        for s in _spans(spans, "wal_commit"):
            if "wave" not in s["args"]:
                assert any(u["tid"] == s["tid"] and u["ts"] <= s["ts"]
                           and s["ts"] + s["dur"] <= u["ts"] + u["dur"]
                           for u in submits), s

    def test_no_new_span_lies_inside_a_stage(self, served_adaptive):
        _, _, spans = served_adaptive
        stages = [s for s in spans if s["name"] in STAGES]
        for s in spans:
            # a span of the 1-us minimum can look nested by rounding alone
            if s["name"] not in NEW_SPANS or s["dur"] <= 1:
                continue
            for t in stages:
                assert not (t["tid"] == s["tid"] and t["ts"] <= s["ts"]
                            and s["ts"] + s["dur"] <= t["ts"] + t["dur"]), \
                    (s, t)

    def test_plan_cache_hits_and_misses_cover_the_fused_groups(
            self, make_engine, obs, events):
        engine = make_engine(obs=obs, max_rounds_per_wave=1)
        client = IntegrationClient(engine)
        client.integrate([harmonic_family(3, 2)], n_samples=3 * R)
        client.integrate([harmonic_family(3, 2), gaussian_family(2, 3)],
                         n_samples=2 * R)
        groups = sum(s["args"]["groups"] for s in _spans(events, "launch"))
        hits = obs.m["plan_cache_hits"].value()
        misses = obs.m["plan_cache_misses"].value()
        assert hits >= 1 and misses >= 2
        assert hits + misses == groups == engine.stats.waves
        builds = [p for s in _spans(events, "launch")
                  for p in s["args"]["parts"] if p[0] == "build"]
        assert len(builds) == groups

    def test_d2h_copies_counter_equals_the_copies_made(
            self, make_engine, obs, monkeypatch):
        import jax

        from repro.service import batcher

        copies = []

        class CountingNumpy:
            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, x, *args, **kwargs):
                if isinstance(x, jax.Array):
                    copies.append(1)
                return np.asarray(x, *args, **kwargs)

        monkeypatch.setattr(batcher, "np", CountingNumpy())
        engine = make_engine(obs=obs)
        client = IntegrationClient(engine)
        client.integrate([harmonic_family(3, 2)], n_samples=3 * R)
        client.integrate([gaussian_family(2, 2), harmonic_family(2, 2)],
                         n_samples=2 * R)
        assert copies
        assert obs.m["d2h_copies"].value() == len(copies)
