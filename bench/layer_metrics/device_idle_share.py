"""Device: 1 - (union of device operation intervals) / traced window,
averaged over the devices used, in percent."""

from bench import tracefile


def read(ctx):
    if ctx.trace is None:
        return None
    share = tracefile.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
