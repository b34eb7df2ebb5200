"""The program's spans on the profiler's clock (``bench/hostclock.py``) and
the per-layer readers of the request path's spans, parts and counters."""

import types

import pytest

from bench import discover, hostclock

NEW = {
    "paper_harmonic_d4.closed2": (
        "submit_ms_per_result.batch", "fusion_build_ms_per_wave.batch",
        "d2h_copies_per_wave.batch", "idle_untraced_share.batch"),
    "genz_corner_vegas_d3.closed4": (
        "submit_ms_per_result.vegas", "queue_ms_per_result.vegas",
        "lock_wait_ms_per_wave.vegas", "fusion_build_ms_per_wave.vegas",
        "d2h_copies_per_wave.vegas", "idle_untraced_share.vegas"),
}


@pytest.fixture(scope="module")
def traced_contexts():
    return {}


@pytest.fixture
def traced_ctx(run_cell, traced_contexts, monkeypatch):
    """The reader context of one traced tiny CPU run per cell, run once
    for the module."""
    def run(workload):
        if workload not in traced_contexts:
            orig = discover.metric_reader

            def reader(name, *a, **kw):
                mod = orig(name, *a, **kw)

                def read(ctx):
                    traced_contexts[workload] = ctx
                    return mod.read(ctx)
                return types.SimpleNamespace(read=read)

            monkeypatch.setattr(discover, "metric_reader", reader)
            rc, line, _ = run_cell(workload, seed=2**31 + 17, trace=True)
            monkeypatch.setattr(discover, "metric_reader", orig)
            assert rc == 0 and line["correct"] is True
        return traced_contexts[workload]

    return run


def _with_device(ctx, clock):
    """``ctx`` with a device plane: one operation during each
    ``device_execute`` span, as the CPU records none."""
    ops = [["XLA Ops", "%fusion", s["ts"] * 1000 - clock["offset_ns"],
            s["dur"] * 1000] for s in ctx.spans
           if s["name"] == "device_execute"]
    trace = dict(ctx.trace, devices={"/device:TPU:0": ops})
    return types.SimpleNamespace(**dict(vars(ctx), trace=trace))


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_cpu_run_aligns_within_a_millisecond(traced_ctx, workload):
    ctx = traced_ctx(workload)
    clock = hostclock.align(ctx)
    assert clock["pairs"] >= 20
    assert clock["residual_ns"] < 1e6
    assert 0 <= clock["first_span_ns"] < ctx.trace["window_ns"]


@pytest.mark.parametrize("workload", sorted(NEW))
def test_each_new_reader_returns_a_value(traced_ctx, workload):
    ctx = traced_ctx(workload)
    ctx = _with_device(ctx, hostclock.align(ctx))
    for name in NEW[workload]:
        value = discover.metric_reader(name).read(ctx)
        assert value is not None and value >= 0, name
    assert discover.metric_reader(NEW[workload][2]).read(ctx) > 0
    states = dict(hostclock.idle_by_worker_state(ctx))
    assert {"launch", "plan"} & set(states)


def _span(name, ts, dur, tid=1, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid,
            "args": args}


def _synthetic():
    """Worker (tid 1) and client (tid 2) spans in µs; a profile that starts
    at program time 50 µs, with one extra ``plan`` before the spans; device
    operations that leave the device idle in [50, 110), [210, 280) and
    [355, 500) µs of program time."""
    offset_ns = 50_000
    spans = [
        _span("lock_wait", 100, 20, wave=0),
        _span("plan", 120, 30, wave=0),
        _span("launch", 200, 100, wave=0,
              parts=[["build", 10, 40], ["dispatch", 60, 30, "k_r2"],
                     ["compile", 65, 10, "k_r2", "backend"]]),
        _span("submit", 250, 200, tid=2, ticket=0, cache="miss"),
        _span("request", 250, 900, tid=2, ticket=0, queue_us=40),
        _span("device_execute", 300, 50, wave=0),
        _span("transfer", 350, 20, wave=0, parts=[["copies", 5, 10]]),
        _span("complete", 400, 30, wave=0),
    ]
    host = [["python", "plan", 80_000 - offset_ns, 5_000]]
    host += [["python", s["name"], s["ts"] * 1000 - offset_ns,
              s["dur"] * 1000] for s in spans
             if s["name"] in ("plan", "launch", "device_execute",
                              "transfer")]
    dev = [["XLA Ops", "%op", a * 1000 - offset_ns, (b - a) * 1000]
           for a, b in ((110, 210), (280, 355), (500, 600))]
    trace = {"window_ns": 600_000 - offset_ns,
             "devices": {"/device:TPU:0": dev}, "host": host}
    return types.SimpleNamespace(spans=spans, trace=trace,
                                 counters={"zmc_waves_total": 1})


def test_align_recovers_the_offset_past_an_extra_profiled_span():
    ctx = _synthetic()
    clock = hostclock.align(ctx)
    assert clock == {"offset_ns": 50_000, "residual_ns": 0.0, "pairs": 4,
                     "first_span_ns": 50_000}
    for e in ctx.trace["host"]:
        e[2] -= 3_000
    assert hostclock.align(ctx)["offset_ns"] == 53_000


def test_idle_by_worker_state_names_the_innermost_state():
    ctx = _synthetic()
    states = dict(hostclock.idle_by_worker_state(ctx))
    us = 1e-6
    assert states == pytest.approx({
        # [50, 100) nothing, [100, 110) lock_wait
        "lock_wait": 10 * us,
        # [210, 250) build, [250, 260) launch, [260, 265) dispatch,
        # [265, 275) compile inside it, [275, 280) dispatch
        "build": 40 * us, "launch": 10 * us, "dispatch": 10 * us,
        "compile": 10 * us,
        # [355, 365) copies inside transfer, [365, 370) transfer,
        # [370, 400) nothing, [400, 430) complete, [430, 500) nothing
        "copies": 10 * us, "transfer": 5 * us, "complete": 30 * us,
        "untraced": (50 + 30 + 70) * us,
    })
    window = ctx.trace["window_ns"] / 1e9
    share = discover.metric_reader("idle_untraced_share.vegas").read(ctx)
    assert share == pytest.approx(100 * states["untraced"] / window)


def test_readers_find_nothing_where_the_program_has_no_new_spans():
    ctx = _synthetic()
    ctx.spans = [s for s in ctx.spans
                 if s["name"] in ("plan", "launch", "device_execute",
                                  "transfer")]
    for s in ctx.spans:
        s["args"].pop("parts", None)
    for name in ("submit_ms_per_result.vegas", "queue_ms_per_result.vegas",
                 "lock_wait_ms_per_wave.vegas",
                 "fusion_build_ms_per_wave.vegas",
                 "d2h_copies_per_wave.vegas"):
        assert discover.metric_reader(name).read(ctx) is None, name
    assert discover.metric_reader("idle_untraced_share.vegas").read(ctx) > 0
