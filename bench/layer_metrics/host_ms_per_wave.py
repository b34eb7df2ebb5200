"""Batcher (service/batcher.py): host milliseconds per wave in the
``launch``, ``transfer`` and ``deposit`` spans, self time."""

from bench.spans import per_wave_ms


def read(ctx):
    return per_wave_ms(ctx, ("launch", "transfer", "deposit"))
