"""The traffic generator: the same seed gives the same requests, client by
client, and the window holds every request submitted in it."""

import threading
import time

import numpy as np

from bench import discover, loadgen
from bench.harness import Cell


def test_draws_are_a_function_of_seed_phase_client_index():
    cfg = discover.config("paper_harmonic_d4")
    a, b = Cell(cfg, 2**31 + 17), Cell(cfg, 2**31 + 17)
    for ids in [(0, 0, 0), (0, 1, 5), (1, 0, 0)]:
        pa, pb = a.draw(*ids), b.draw(*ids)
        assert all(np.array_equal(pa[k], pb[k]) for k in pa)
    other = Cell(cfg, 2**31 + 18).draw(0, 0, 0)
    assert not np.array_equal(a.draw(0, 0, 0)["k"], other["k"])
    # the warm-up's phase never repeats the window's draws
    assert not np.array_equal(a.draw(loadgen.PHASE_WARMUP, 0, 0)["a"],
                              a.draw(loadgen.PHASE_WINDOW, 0, 0)["a"])


def test_closed_loop_keeps_one_request_in_flight_per_client():
    in_flight = {}
    most = {}
    lock = threading.Lock()

    def serve(params):
        c = params["client"]
        with lock:
            in_flight[c] = in_flight.get(c, 0) + 1
            most[c] = max(most.get(c, 0), in_flight[c])
        time.sleep(0.01)
        with lock:
            in_flight[c] -= 1
        return object()

    def draw(phase, c, i):
        return {"phase": phase, "client": c, "index": i}

    mix = {"loop": "closed", "clients": 3, "warmup_per_client": 2}
    t0, t1, recs = loadgen.closed_loop(serve, draw, mix, 0.3, late_s=5)
    assert 0.3 <= t1 - t0 < 0.5
    assert set(most) == {0, 1, 2} and max(most.values()) == 1
    assert all(r.params["phase"] == loadgen.PHASE_WINDOW for r in recs)
    for c in range(3):
        idx = [r.index for r in recs if r.client == c]
        assert idx == list(range(len(idx))) and len(idx) >= 5
    assert all(r.ok and t0 <= r.submit_t <= t1 for r in recs)


def test_a_request_that_never_returns_is_recorded_without_result():
    gate = threading.Event()

    def serve(params):
        if params["i"] == 1:
            gate.wait(10)
        return object()

    t0, t1, recs = loadgen.closed_loop(
        serve, lambda ph, c, i: {"i": i},
        {"loop": "closed", "clients": 1}, 0.1, late_s=0.2)
    gate.set()
    assert [r.ok for r in recs] == [True, False]
