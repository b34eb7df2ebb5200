"""Genz (1984) corner peak instances: a_i = U(0, 1) + 0.1 per function and
axis, scaled so that sum_i a_i equals the difficulty (the law of the
system's own ``genz.corner_peak``, drawn here from the benchmark's seed)."""

from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, request: dict) -> dict:
    n, dim = int(request["n_fn"]), int(request["dim"])
    a = rng.uniform(0.0, 1.0, (n, dim)) + 0.1
    a = a * (float(request["difficulty"]) / a.sum(axis=1, keepdims=True))
    return {"a": a.astype(np.float32)}


def family(params: dict, request: dict):
    import jax.numpy as jnp

    from repro.core import IntegrandFamily

    n, dim = int(request["n_fn"]), int(request["dim"])

    def fn(x, p):
        return (1.0 + jnp.sum(p["a"] * x, axis=-1)) ** (-(dim + 1.0))

    box = np.broadcast_to(np.asarray([0.0, 1.0], np.float32),
                          (n, dim, 2)).copy()
    return IntegrandFamily(fn=fn, params={"a": jnp.asarray(params["a"])},
                           domains=jnp.asarray(box),
                           name=f"genz_corner[{n}x{dim}]",
                           kernel="mc_eval_genz_corner").validate()
