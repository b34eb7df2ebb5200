"""Engine (service/engine.py): milliseconds from a request's admission to
the first launch that carries any of its rounds, the ``request`` span's
``queue_us``, averaged over the requests submitted in the window."""


def read(ctx):
    q = [s["args"]["queue_us"] for s in ctx.spans
         if s["name"] == "request"
         and s["args"].get("queue_us") is not None]
    return sum(q) / len(q) / 1e3 if q else None
