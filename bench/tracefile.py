"""Reduction of a profiler trace to device time, idle share and gaps.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
dict, the form the readers and the recorded test trace share:

    {"window_ns": W,
     "devices": {"/device:TPU:0": [[line, name, start_ns, dur_ns], ...]},
     "host": [[line, name, start_ns, dur_ns], ...]}

Times count from the start of the trace.  Device operations are the events
of each device plane's ``XLA Ops`` line; host spans are the service's stage
annotations (``plan``, ``launch``, ``transfer``, ...) on the host plane.
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_OPS_LINE = "XLA Ops"
# the service's wave stages, as its tracer annotates them into the trace
HOST_STAGES = ("plan", "launch", "device_execute", "transfer", "deposit",
               "wal_commit")


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str, window_ns: int) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"window_ns": int(window_ns), "devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = out["devices"].setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for ev in line.events:
                    # a TPU op's event name is its whole HLO instruction:
                    # keep the instruction's name
                    evs.append([line.name, ev.name.split(" = ", 1)[0],
                                int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_STAGES:
                        out["host"].append([line.name, ev.name,
                                            int(ev.start_ns),
                                            int(ev.duration_ns)])
    # devices the run used only: a plane with no operation is another
    # chip of the host, or none
    out["devices"] = {name: evs for name, evs in out["devices"].items()
                      if evs}
    return out


def device_ops(trace: dict, dev: str) -> list:
    return [e for e in trace["devices"][dev] if e[0] == DEVICE_OPS_LINE]


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted intervals covering the same points."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(trace: dict, dev: str) -> int:
    w = trace["window_ns"]
    ivs = _clip(((e[2], e[2] + e[3]) for e in device_ops(trace, dev)), 0, w)
    return sum(e - s for s, e in union(ivs))


def busy_s(trace: dict) -> float | None:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = list(trace["devices"])
    if not devs:
        return None
    return sum(busy_ns(trace, d) for d in devs) / len(devs) / 1e9


def idle_share(trace: dict) -> float | None:
    devs = list(trace["devices"])
    if not devs or trace["window_ns"] <= 0:
        return None
    w = trace["window_ns"]
    return sum(1.0 - busy_ns(trace, d) / w for d in devs) / len(devs)


def op_ns(trace: dict, match) -> int:
    """Device nanoseconds, summed over devices, of the operations whose
    name ``match`` accepts, clipped to the window."""
    w = trace["window_ns"]
    total = 0
    for dev in trace["devices"]:
        for _, name, s, d in device_ops(trace, dev):
            if match(name):
                total += max(0, min(s + d, w) - max(s, 0))
    return total


def top_ops(trace: dict, k: int = 10) -> list:
    """[name, seconds] of the device operations that took most time,
    summed over devices."""
    by: dict[str, int] = {}
    w = trace["window_ns"]
    for dev in trace["devices"]:
        for _, name, s, d in device_ops(trace, dev):
            by[name] = by.get(name, 0) + max(0, min(s + d, w) - max(s, 0))
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(trace: dict, dev: str) -> list[tuple[int, int]]:
    """The intervals of the window in which no operation ran on ``dev``."""
    w = trace["window_ns"]
    gaps, t = [], 0
    for s, e in union(_clip(((e[2], e[2] + e[3])
                             for e in device_ops(trace, dev)), 0, w)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w:
        gaps.append((t, w))
    return gaps


def gaps_by_host_stage(trace: dict, k: int = 10) -> list:
    """[name, seconds] of device idle time, averaged over devices, named by
    the service stage open on the host in it (the one covering most of
    each gap; ``no stage`` where none was open)."""
    spans = [(e[2], e[2] + e[3], e[1]) for e in trace["host"]
             if e[1] in HOST_STAGES]
    spans.sort()
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    devs = list(trace["devices"])
    by: dict[str, int] = {}
    for dev in devs:
        for gs, ge in idle_gaps(trace, dev):
            cover: dict[str, int] = {}
            for s, e, name in spans[bisect.bisect_left(starts, gs - longest):]:
                if s >= ge:
                    break
                o = min(e, ge) - max(s, gs)
                if o > 0:
                    cover[name] = cover.get(name, 0) + o
            name = max(cover, key=cover.get) if cover else "no stage"
            by[name] = by.get(name, 0) + (ge - gs)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / len(devs) / 1e9] for name, ns in top]
