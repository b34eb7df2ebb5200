"""Engine (service/engine.py): milliseconds per wave the worker waits for
the engine lock, the ``lock_wait`` spans."""

from bench.spans import per_wave_ms


def read(ctx):
    if not any(s["name"] == "lock_wait" for s in ctx.spans):
        return None
    return per_wave_ms(ctx, ("lock_wait",))
