"""The program's clock and the profiler's, on one timeline.

The service's tracer takes each span's start just before it enters the
profiler annotation of the same name (``repro.obs.trace``), so the six
stage spans are recorded twice: among the program's spans (``ctx.spans``:
``ts`` and ``dur`` in microseconds of the monotonic clock) and on the
profiler's host plane (``ctx.trace["host"]``: nanoseconds from the start of
the trace).  ``align`` matches the two records by name and order and
returns the offset between the clocks.  ``idle_by_worker_state`` uses it to
lay every span and part of the engine's worker on the device timeline, and
names each stretch of device idle time by what the worker was doing then.
"""

from __future__ import annotations

import collections
import heapq

import numpy as np

from bench import tracefile

# order shifts tried beyond the difference in count of the two records
MAX_SHIFT = 64
UNTRACED = "untraced"


def _best_shift(prog: np.ndarray, prof: np.ndarray):
    """Offsets (program ns - profiler ns) of the pairs at the order shift
    whose median absolute residual is least, and that residual.  The
    profile runs a little longer than the window the program's spans are
    taken from, so a shift has to keep at least half of the shorter
    record paired."""
    need = max(1, (min(len(prog), len(prof)) + 1) // 2)
    best = None
    for k in range(-MAX_SHIFT, len(prof) - len(prog) + MAX_SHIFT + 1):
        lo, hi = max(0, -k), min(len(prog), len(prof) - k)
        if hi - lo < need:
            continue
        d = prog[lo:hi] - prof[lo + k:hi + k]
        res = float(np.median(np.abs(d - np.median(d))))
        if best is None or (res, -len(d)) < (best[0], -len(best[1])):
            best = (res, d)
    return best


def align(ctx) -> dict | None:
    """``{"offset_ns", "residual_ns", "pairs", "first_span_ns"}``: a
    program time of ``t`` microseconds lies at ``t * 1000 - offset_ns`` on
    the profiler's clock; ``residual_ns`` is the median absolute residual
    of the ``pairs`` stage spans matched, and ``first_span_ns`` where the
    window's first program span lies on the profiler's clock (its lead on
    the profile's start).  None without a trace or a stage span in both."""
    if ctx.trace is None or not ctx.spans:
        return None
    found = []
    for name in tracefile.HOST_STAGES:
        prog = np.sort(np.array([s["ts"] * 1000 for s in ctx.spans
                                 if s["name"] == name], np.int64))
        prof = np.sort(np.array([e[2] for e in ctx.trace["host"]
                                 if e[1] == name], np.int64))
        if len(prog) and len(prof):
            found.append(_best_shift(prog, prof)[1])
    if not found:
        return None
    d = np.concatenate(found)
    offset = int(np.median(d))
    return {"offset_ns": offset,
            "residual_ns": float(np.median(np.abs(d - offset))),
            "pairs": int(len(d)),
            "first_span_ns": min(s["ts"] for s in ctx.spans) * 1000 - offset}


def worker_tid(spans) -> int | None:
    """The thread that opens ``launch``: the engine's worker."""
    tids = collections.Counter(s["tid"] for s in spans
                               if s["name"] == "launch")
    return tids.most_common(1)[0][0] if tids else None


def worker_intervals(ctx, offset_ns: int) -> list[tuple[int, int, str]]:
    """(start, end, name) on the profiler's clock, in ns, of every span the
    worker opened and every part of those spans."""
    tid = worker_tid(ctx.spans)
    out = []
    for s in ctx.spans:
        if s["tid"] != tid:
            continue
        t0 = s["ts"] * 1000 - offset_ns
        out.append((t0, t0 + s["dur"] * 1000, s["name"]))
        for part in s.get("args", {}).get("parts", ()):
            p0 = t0 + part[1] * 1000
            if part[2] > 0:
                out.append((p0, p0 + part[2] * 1000, part[0]))
    return out


def name_gaps(intervals, gaps) -> dict[str, int]:
    """Nanoseconds of ``gaps`` (sorted, disjoint) by the innermost interval
    open in them: of those open at an instant, the one that opened last
    (the shorter on a tie); ``untraced`` where none is open."""
    starts = sorted(intervals)
    by: dict[str, int] = {}
    heap: list = []   # (-start, end, name): the innermost on top
    i = 0
    for gs, ge in gaps:
        t = gs
        while t < ge:
            while i < len(starts) and starts[i][0] <= t:
                s, e, name = starts[i]
                heapq.heappush(heap, (-s, e, name))
                i += 1
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)
            nxt = min(ge, starts[i][0] if i < len(starts) else ge,
                      heap[0][1] if heap else ge)
            name = heap[0][2] if heap else UNTRACED
            by[name] = by.get(name, 0) + (nxt - t)
            t = nxt
    return by


def idle_by_worker_state(ctx) -> list | None:
    """``[[state, seconds], ...]``, most first: the device's idle time in
    the window, averaged over the devices, by the innermost span or part
    open on the worker (``untraced`` where none was).  None without a
    device plane, or where the clocks cannot be aligned."""
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    clock = align(ctx)
    if clock is None or worker_tid(ctx.spans) is None:
        return None
    ivs = worker_intervals(ctx, clock["offset_ns"])
    devs = list(ctx.trace["devices"])
    total: dict[str, int] = {}
    for dev in devs:
        for name, ns in name_gaps(ivs,
                                  tracefile.idle_gaps(ctx.trace, dev)).items():
            total[name] = total.get(name, 0) + ns
    return [[name, ns / len(devs) / 1e9]
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])]
