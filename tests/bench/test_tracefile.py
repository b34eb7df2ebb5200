"""The reduction from a profiler trace to device busy time, idle share,
kernel and collective time, and idle gaps named by the host's stage."""

import gzip
import json

import numpy as np
import pytest

from bench import discover, tracefile

OPS = tracefile.DEVICE_OPS_LINE


def _synthetic():
    # one device, window 100 ns: ops [10,30) and [20,40) overlap, [60,70)
    # and one op that runs past the window's end
    return {"window_ns": 100,
            "devices": {"/device:TPU:0": [
                [OPS, "mc_eval_fused_a", 10, 20],
                [OPS, "all-reduce.1", 20, 20],
                [OPS, "copy.2", 60, 10],
                [OPS, "mc_eval_fused_b", 95, 20],
                ["XLA Modules", "jit_x", 0, 100]]},
            "host": [["python", "transfer", 40, 15],
                     ["python", "plan", 50, 12],
                     ["python", "launch", 70, 30]]}


def test_busy_idle_and_op_time_on_a_small_trace():
    t = _synthetic()
    # busy: [10,40) + [60,70) + [95,100) = 45 of 100
    assert tracefile.busy_ns(t, "/device:TPU:0") == 45
    assert tracefile.idle_share(t) == pytest.approx(0.55)
    assert tracefile.busy_s(t) == pytest.approx(45e-9)
    assert tracefile.op_ns(t, lambda n: "mc_eval_fused" in n) == 25
    assert tracefile.op_ns(t, lambda n: "all-reduce" in n) == 20
    assert tracefile.top_ops(t)[0] == ["mc_eval_fused_a", 20e-9]


def test_idle_gaps_are_named_by_the_stage_open_on_the_host():
    t = _synthetic()
    assert tracefile.idle_gaps(t, "/device:TPU:0") == [(0, 10), (40, 60),
                                                      (70, 95)]
    named = dict(tracefile.gaps_by_host_stage(t))
    # [40,60): transfer covers 15, plan 10; [70,95): launch; [0,10): none
    assert named == {"transfer": pytest.approx(20e-9),
                     "launch": pytest.approx(25e-9),
                     "no stage": pytest.approx(10e-9)}
    assert sum(named.values()) == pytest.approx(
        tracefile.idle_share(t) * t["window_ns"] / 1e9)


def _mask_busy(trace, dev):
    w = trace["window_ns"]
    step = max(1, w // 2_000_000)
    grid = np.zeros(w // step + 1, bool)
    for e in tracefile.device_ops(trace, dev):
        s, d = e[2], e[3]
        grid[max(0, s) // step:max(0, min(s + d, w)) // step] = True
    return grid.sum() * step


RECORDED = discover.BENCH / "testdata" / "trace_paper_closed2.json.gz"


def test_recorded_chip_trace_reduces_consistently():
    with gzip.open(RECORDED, "rt") as f:
        t = json.load(f)
    devs = list(t["devices"])
    assert devs
    for dev in devs:
        busy = tracefile.busy_ns(t, dev)
        # the interval union agrees with a coarse mask of the same ops
        assert busy == pytest.approx(_mask_busy(t, dev),
                                     rel=0.02, abs=t["window_ns"] // 500)
        gaps = tracefile.idle_gaps(t, dev)
        assert sum(e - s for s, e in gaps) == t["window_ns"] - busy
    kernel = tracefile.op_ns(t, lambda n: "mc_eval_fused" in n)
    assert 0 < kernel <= sum(tracefile.busy_ns(t, d) for d in devs)
    named = tracefile.gaps_by_host_stage(t)
    assert sum(s for _, s in named) == pytest.approx(
        tracefile.idle_share(t) * t["window_ns"] / 1e9, rel=1e-9)
    assert {name for name, _ in named} <= set(tracefile.HOST_STAGES) | {
        "no stage"}


def test_kernel_reader_reads_launch_shapes_from_the_recorded_trace():
    from bench.harness import Context
    with gzip.open(RECORDED, "rt") as f:
        t = json.load(f)
    ctx = Context("paper_harmonic_d4.closed2",
                  discover.config("paper_harmonic_d4"), 2.5, [], [], [], {},
                  0, t)
    ns = discover.metric_reader("kernel_ns_per_fn_sample").read(ctx)
    # every launch in the trace is one or two 1000-integrand batches at
    # 8 x 131072 samples: kernel time over those function-samples
    launches = [e for e in tracefile.device_ops(t, "/device:TPU:0")
                if "mc_eval_fused" in e[1] and e[2] + e[3] <= t["window_ns"]]
    per = {"f1008": 1000, "f2016": 2000}
    samples = sum(next(v for k, v in per.items() if k in e[1]) * 8 * 131072
                  for e in launches)
    assert ns == pytest.approx(sum(e[3] for e in launches) / samples)
