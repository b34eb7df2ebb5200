"""Engine (service/engine.py ``submit``): host milliseconds of the
``submit`` span's self time (canonicalize, hash, grid pilot, admission) per
request submitted in the window, on the client's thread."""

from bench.spans import self_us


def read(ctx):
    # a request span starts with its submit and may end inside it (a hit)
    spans = [s for s in ctx.spans if s["name"] != "request"]
    n = sum(1 for s in spans if s["name"] == "submit")
    if not n:
        return None
    return self_us(spans, ("submit",)) / 1e3 / n
