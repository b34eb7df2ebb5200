"""Device: the share of the window, in percent, in which the device was
idle and no span or part was open on the engine's worker
(``hostclock.idle_by_worker_state``)."""

from bench import hostclock


def read(ctx):
    states = hostclock.idle_by_worker_state(ctx)
    if states is None:
        return None
    untraced = dict(states).get(hostclock.UNTRACED, 0.0)
    return 100.0 * untraced / (ctx.trace["window_ns"] / 1e9)
