"""Self time of the service's spans (trace-event dicts: ``ts``/``dur`` in
microseconds, ``tid``)."""

from __future__ import annotations

from bench.tracefile import union


def self_us(spans: list[dict], names) -> float:
    """Summed self time of the spans named in ``names``: each span's
    duration less the part of it that spans nested inside it, on the same
    thread, cover."""
    names = set(names)
    by_tid: dict[int, list[dict]] = {}
    for s in spans:
        by_tid.setdefault(s["tid"], []).append(s)
    total = 0.0
    for group in by_tid.values():
        group.sort(key=lambda s: (s["ts"], -s["dur"]))
        for i, s in enumerate(group):
            if s["name"] not in names:
                continue
            s0, s1 = s["ts"], s["ts"] + s["dur"]
            inner = []
            for t in group[i + 1:]:
                if t["ts"] >= s1:
                    break
                if t["ts"] + t["dur"] <= s1:
                    inner.append((t["ts"], t["ts"] + t["dur"]))
            total += (s1 - s0) - sum(e - b for b, e in union(inner))
    return total


def per_wave_ms(ctx, names) -> float | None:
    waves = ctx.counters.get("zmc_waves_total", 0)
    if not waves or not ctx.spans:
        return None
    return self_us(ctx.spans, names) / 1e3 / waves
