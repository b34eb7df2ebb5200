"""Kernel (kernels/template.py ``fused_mc_pallas``): device nanoseconds of
the fused kernel's launches, summed over devices, per function-sample those
launches evaluated.

A launch's name is its bucket's shape (``multi.plan_spec``):
``mc_eval_fused_<sampler>_d<dim>f<rows>c<cols>_r<rounds>``, with ``rows``
the padded functions of every stream in it.  Every stream of a cell holds
``n_fn`` functions, padded to the kernel's 16-row blocks, so a launch
evaluates ``rows // pad(n_fn) * n_fn`` functions at ``rounds`` rounds of
``round_samples``.  Launches are counted on one device (a sharded launch
runs once on each) and only where they lie wholly inside the window.
"""

import re

from bench import tracefile

SHAPE = re.compile(r"mc_eval_fused_[a-z]+_d\d+f(\d+)c\d+_r(\d+)")
F_BLK = 16


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    n_fn = int(ctx.config["request"]["n_fn"])
    per_stream = -(-n_fn // F_BLK) * F_BLK
    round_samples = int(ctx.config["engine"]["round_samples"])
    w = ctx.trace["window_ns"]
    ns = samples = 0
    for i, dev in enumerate(sorted(ctx.trace["devices"])):
        for _, name, s, d in tracefile.device_ops(ctx.trace, dev):
            m = SHAPE.search(name)
            if m is None or s < 0 or s + d > w:
                continue
            ns += d
            if i == 0:
                rows, rounds = int(m.group(1)), int(m.group(2))
                samples += (rows // per_stream) * n_fn * rounds \
                    * round_samples
    if not ns or not samples:
        return None
    return ns / samples
