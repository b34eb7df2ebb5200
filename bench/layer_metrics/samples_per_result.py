"""Engine adaptation (core/adaptive.py): mean function-samples per
integrand at completion, over the answers completed in the window."""


def read(ctx):
    ns = [float(n) for r in ctx.completed for n in r.result.n_per_family]
    return sum(ns) / len(ns) if ns else None
