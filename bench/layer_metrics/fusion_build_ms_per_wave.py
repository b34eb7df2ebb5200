"""Batcher (service/batcher.py ``_plan_for``): milliseconds per wave in the
``build`` parts of the ``launch`` span: a fusion plan built and uploaded on
a plan-cache miss, a lookup on a hit."""


def read(ctx):
    waves = ctx.counters.get("zmc_waves_total", 0)
    builds = [p[2] for s in ctx.spans if s["name"] == "launch"
              for p in s["args"].get("parts", ()) if p[0] == "build"]
    if not waves or not builds:
        return None
    return sum(builds) / 1e3 / waves
