"""The one traffic generator: it reads a mix from ``traffic/<name>.json``.

A mix with ``"loop": "closed"`` runs ``clients`` client threads, each with
one request in flight: it submits, waits for the answer, and submits the
next.  Each client first serves ``warmup_per_client`` requests of its own
(set-up), then all clients start the measured window together.  Request
``i`` of client ``c`` is drawn from ``(seed, phase, c, i)`` alone, so the
same seed gives the same requests in the same order on every client, and
the warm-up's requests (another phase) never repeat the window's.
"""

from __future__ import annotations

import dataclasses
import threading
import time

PHASE_WINDOW = 0
PHASE_WARMUP = 1


@dataclasses.dataclass
class Record:
    """One request: its parameters, and when and what came back."""
    client: int
    index: int
    params: dict
    submit_t: float | None = None
    done_t: float | None = None
    result: object = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.result is not None and not getattr(
            self.result, "failed", False)


def closed_loop(serve, draw, mix: dict, seconds: float, *,
                on_window_start=lambda: None, on_window_end=lambda: None,
                late_s: float = 60.0, clock=time.monotonic):
    """Drive ``serve(params) -> result`` from ``mix["clients"]`` threads.

    ``draw(phase, client, index) -> params`` makes each request.  Returns
    ``(t0, t1, records)``: the window's bounds on ``clock`` and a record of
    every request submitted inside it.  Requests still in flight when the
    window closes are waited for up to ``late_s`` seconds; one that has
    not come back by then keeps ``result`` None.
    """
    if mix.get("loop") != "closed":
        raise ValueError(f"traffic loop {mix.get('loop')!r} is not supported "
                         "by this generator")
    n_clients = int(mix["clients"])
    warm = int(mix.get("warmup_per_client", 0))
    ready = threading.Barrier(n_clients + 1)
    go = threading.Event()
    stop = threading.Event()
    records: list[Record] = []
    lock = threading.Lock()
    errors: list[str] = []

    def client(c: int) -> None:
        try:
            for i in range(warm):
                serve(draw(PHASE_WARMUP, c, i))
        except Exception as exc:          # reported as a set-up failure
            errors.append(f"client {c} warm-up: {exc!r}")
            ready.abort()
            return
        try:
            ready.wait()
        except threading.BrokenBarrierError:
            return
        go.wait()
        i = 0
        while not stop.is_set():
            rec = Record(client=c, index=i, params=draw(PHASE_WINDOW, c, i))
            with lock:
                records.append(rec)
            rec.submit_t = clock()
            try:
                rec.result = serve(rec.params)
            except Exception as exc:      # counted as a failed request
                rec.error = repr(exc)
            rec.done_t = clock()
            i += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(n_clients)]
    for t in threads:
        t.start()
    try:
        ready.wait()
    except threading.BrokenBarrierError:
        for t in threads:
            t.join()
        raise RuntimeError("; ".join(errors) or "warm-up failed")
    on_window_start()
    t0 = clock()
    go.set()
    time.sleep(max(0.0, t0 + seconds - clock()))
    t1 = clock()
    stop.set()
    on_window_end()
    deadline = clock() + late_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - clock()))
    return t0, t1, list(records)
