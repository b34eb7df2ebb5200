"""Batcher (service/batcher.py ``transfer``): device-to-host reads per wave,
``zmc_d2h_copies_total`` over ``zmc_waves_total``."""


def read(ctx):
    copies = ctx.counters.get("zmc_d2h_copies_total")
    waves = ctx.counters.get("zmc_waves_total", 0)
    if copies is None or not waves:
        return None
    return copies / waves
