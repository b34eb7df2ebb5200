"""Store (service/store.py): milliseconds per wave in the ``wal_commit``
span, the journal's write and fsync."""

from bench.spans import per_wave_ms


def read(ctx):
    if not any(s["name"] == "wal_commit" for s in ctx.spans):
        return None
    return per_wave_ms(ctx, ("wal_commit",))
