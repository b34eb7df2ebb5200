"""Engine (service/engine.py): host milliseconds per wave in the ``plan``
span, grid refits included, self time."""

from bench.spans import per_wave_ms


def read(ctx):
    return per_wave_ms(ctx, ("plan",))
