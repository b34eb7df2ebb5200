"""Tracer + trace artifact: Chrome-trace events, crash-tolerant JSONL,
and the fake clock driving deterministic timestamps."""

import json

import pytest

from repro.obs import clock
from repro.obs.trace import (STAGES, NULL, JsonlWriter, NullTracer, Tracer,
                             load_trace, span_totals)


@pytest.fixture
def fake_clock():
    """A controllable second-counter driving monotonic/wall readings."""
    state = {"t": 100.0}

    def advance(dt):
        state["t"] += dt

    clock.set_clock(lambda: state["t"])
    yield advance
    clock.set_clock(None)


class TestStages:
    def test_six_stages_in_causal_order(self):
        assert STAGES == ("plan", "launch", "device_execute", "transfer",
                          "deposit", "wal_commit")


class TestNullTracer:
    def test_disabled_and_shared_span(self):
        assert NullTracer.enabled is False
        s1, s2 = NULL.span("a"), NULL.span("b", x=1)
        assert s1 is s2            # one shared no-op context manager
        with s1:
            pass
        assert NULL.instant("x") is None


class TestTracer:
    def test_span_emits_complete_event(self, fake_clock):
        events = []
        tracer = Tracer(events.append)
        with tracer.span("launch", wave=3, items=7):
            fake_clock(0.002)
        (ev,) = events
        assert ev["ph"] == "X"
        assert ev["name"] == "launch"
        assert ev["dur"] == 2000            # µs, from the fake clock
        assert ev["ts"] == int(100.0 * 1e6)
        assert ev["args"] == {"wave": 3, "items": 7}

    def test_instant_event(self, fake_clock):
        events = []
        tracer = Tracer(events.append)
        tracer.instant("wave_restart", wave=5, streams=["abc"])
        (ev,) = events
        assert ev["ph"] == "i"
        assert ev["s"] == "t"
        assert ev["args"] == {"wave": 5, "streams": ["abc"]}

    def test_multiple_sinks_all_receive(self, fake_clock):
        a, b = [], []
        tracer = Tracer(a.append)
        tracer.add_sink(b.append)
        with tracer.span("plan"):
            pass
        assert len(a) == len(b) == 1

    def test_span_emits_on_exception(self, fake_clock):
        events = []
        tracer = Tracer(events.append)
        with pytest.raises(RuntimeError):
            with tracer.span("deposit"):
                raise RuntimeError("wave died")
        assert events and events[0]["name"] == "deposit"


class TestJsonlWriter:
    def test_round_trip(self, tmp_path, fake_clock):
        path = str(tmp_path / "trace.json")
        writer = JsonlWriter(path)
        tracer = Tracer(writer)
        with tracer.span("plan", wave=0):
            fake_clock(0.001)
        tracer.instant("straggler", wave=0)
        tracer.close()
        events = load_trace(path)
        assert [e["name"] for e in events] == ["plan", "straggler"]
        assert writer.n_events == 2

    def test_unclosed_file_still_loads(self, tmp_path, fake_clock):
        # the crash-tolerance property: a SIGKILLed process leaves a
        # headless array that load_trace (and Perfetto) accept
        path = str(tmp_path / "trace.json")
        writer = JsonlWriter(path)
        tracer = Tracer(writer)
        with tracer.span("launch"):
            pass
        writer.flush()                      # no close(): simulated crash
        events = load_trace(path)
        assert [e["name"] for e in events] == ["launch"]

    def test_loads_as_plain_json_after_patching_tail(self, tmp_path,
                                                     fake_clock):
        # what Perfetto effectively does: tolerate the trailing comma
        path = str(tmp_path / "trace.json")
        tracer = Tracer(JsonlWriter(path))
        with tracer.span("transfer"):
            pass
        tracer.close()
        text = open(path).read().strip().rstrip(",") + "]"
        assert json.loads(text)[0]["name"] == "transfer"

    def test_closed_array_loads_too(self, tmp_path):
        path = str(tmp_path / "t.json")
        with open(path, "w") as f:
            json.dump([{"ph": "X", "name": "plan", "dur": 5}], f)
        assert load_trace(path)[0]["name"] == "plan"

    def test_empty_trace(self, tmp_path):
        path = str(tmp_path / "t.json")
        JsonlWriter(path).close()
        assert load_trace(path) == []


class TestSpanTotals:
    def test_aggregates_complete_events_only(self):
        events = [
            {"ph": "X", "name": "launch", "dur": 2_000_000},
            {"ph": "X", "name": "launch", "dur": 1_000_000},
            {"ph": "X", "name": "deposit", "dur": 500_000},
            {"ph": "i", "name": "straggler"},
        ]
        totals = span_totals(events)
        assert totals == {"launch": 3.0, "deposit": 0.5}


class TestObservabilityBundle:
    def test_disabled_is_null_traced_but_counted(self):
        from repro.obs import Observability
        obs = Observability.disabled()
        assert obs.tracing is False
        assert obs.record_convergence is False
        obs.m["launches"].inc(3)
        assert obs.m["launches"].value() == 3

    def test_enabled_spans_feed_stage_histogram(self, fake_clock):
        from repro.obs import Observability
        events = []
        obs = Observability.enabled(sinks=(events.append,))
        assert obs.tracing is True and obs.record_convergence is True
        with obs.span("deposit", items=4):
            fake_clock(0.01)
        with obs.span("not_a_stage"):
            fake_clock(0.01)
        # trace got both; the per-stage histogram only the pipeline stage
        assert [e["name"] for e in events] == ["deposit", "not_a_stage"]
        assert obs.m["stage_seconds"].count(stage="deposit") == 1
        assert obs.m["stage_seconds"].sum(stage="deposit") == \
            pytest.approx(0.01)

    def test_enabled_writes_trace_file(self, tmp_path, fake_clock):
        from repro.obs import Observability
        path = str(tmp_path / "trace.json")
        obs = Observability.enabled(trace_path=path)
        obs.event("wave_restart", wave=1)
        obs.close()
        assert [e["name"] for e in load_trace(path)] == ["wave_restart"]


class TestClockShim:
    def test_fake_clock_drives_all_three_readings(self, fake_clock):
        t0 = (clock.monotonic(), clock.monotonic_ns(), clock.wall())
        assert t0 == (100.0, int(100.0 * 1e9), 100.0)
        fake_clock(1.5)
        assert clock.monotonic() == 101.5
        assert clock.wall() == 101.5

    def test_real_clock_restored(self):
        clock.set_clock(None)
        a = clock.monotonic()
        b = clock.monotonic()
        assert b >= a
        assert clock.monotonic_ns() > 0


class TestAfterTheFactAndParts:
    def test_complete_emits_span_on_given_tid(self, fake_clock):
        events = []
        tracer = Tracer(events.append)
        tracer.complete("request", 100_000_000_000, 100_002_500_000,
                        tid=77, ticket=3, queue_us=12)
        (ev,) = events
        assert (ev["ph"], ev["name"], ev["tid"]) == ("X", "request", 77)
        assert (ev["ts"], ev["dur"]) == (100_000_000, 2500)
        assert ev["args"] == {"ticket": 3, "queue_us": 12}

    def test_null_tracer_complete_is_a_noop(self):
        assert NULL.complete("request", 0, 10, tid=1, ticket=0) is None

    def test_parts_lie_inside_their_span(self, fake_clock):
        events = []
        tracer = Tracer(events.append)
        with tracer.span("launch") as span:
            fake_clock(0.001)
            with span.part("build"):
                fake_clock(0.0025)
            with span.part("dispatch", "mc_eval_fused_mc_d3f16c54_r2"):
                fake_clock(0.004)
            fake_clock(0.0005)
        (ev,) = events
        assert ev["args"]["parts"] == [
            ["build", 1000, 2500],
            ["dispatch", 3500, 4000, "mc_eval_fused_mc_d3f16c54_r2"]]
        for _, off, dur, *_ in ev["args"]["parts"]:
            assert 0 <= off and off + dur <= ev["dur"]

    def test_current_part_is_the_innermost_open_one(self, fake_clock):
        from repro.obs.trace import current_part
        tracer = Tracer(lambda ev: None)
        assert current_part() is None
        with tracer.span("launch") as span:
            with span.part("dispatch", "b") as outer:
                assert current_part() is outer
                with span.part("unpack") as inner:
                    assert current_part() is inner
                assert current_part() is outer
        assert current_part() is None

    def test_wave_binding_reaches_nested_spans(self, fake_clock):
        events = []
        tracer = Tracer(events.append)
        with tracer.wave(7):
            with tracer.span("deposit"):
                with tracer.span("wal_commit", bytes=10):
                    pass
            with tracer.span("plan", wave=8):
                pass
        with tracer.span("launch"):
            pass
        assert [e["args"].get("wave") for e in events] == [7, 7, 8, None]

    def test_disabled_bundle_emits_nothing_and_records_no_parts(
            self, fake_clock):
        from repro.obs import Observability
        obs = Observability.disabled()
        span = obs.span("launch", items=3)
        assert span is NULL.span("plan")       # the shared no-op
        with span:
            with span.part("build") as part:
                assert part is span            # no part object, no clock
            span.set(ticket=1)
        with obs.wave(3):
            obs.complete("request", 0, 10)
        assert not hasattr(span, "parts")
        obs.close()


class TestCompileListener:
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def test_counts_phases_and_marks_the_open_part(self, fake_clock):
        import jax.monitoring
        from repro.obs import Observability
        events = []
        obs = Observability.enabled(sinks=(events.append,))
        try:
            with obs.span("launch") as span:
                with span.part("dispatch", "mc_eval_fused_mc_d3f16c54_r2"):
                    fake_clock(0.003)
                    for ev, dur in zip(self.EVENTS, (0.001, 0.0005, 0.002)):
                        jax.monitoring.record_event_duration_secs(ev, dur)
            jax.monitoring.record_event_duration_secs(self.EVENTS[2], 0.25)
        finally:
            obs.close()
        assert obs.m["backend_compiles"].value() == 2
        hist = obs.m["compile_seconds"]
        assert hist.count(phase="trace") == hist.count(phase="lower") == 1
        assert hist.sum(phase="backend") == pytest.approx(0.252)
        (launch,) = events
        compiles = [p for p in launch["args"]["parts"] if p[0] == "compile"]
        assert [p[3:] for p in compiles] == [
            ["mc_eval_fused_mc_d3f16c54_r2", ph]
            for ph in ("trace", "lower", "backend")]
        assert [p[2] for p in compiles] == [1000, 500, 2000]
        # closed: the listener is gone, so later compiles are not counted
        jax.monitoring.record_event_duration_secs(self.EVENTS[2], 0.1)
        assert obs.m["backend_compiles"].value() == 2
        obs.close()                                   # idempotent
