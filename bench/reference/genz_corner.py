"""Genz (1984) corner peak f(x) = (1 + a.x)^-(d+1) over [0,1]^d, in closed
form.

For m > d and a_i > 0, by inclusion-exclusion over the corners S of the cube:
  int (1 + a.x)^-m dx = sum_S (-1)^|S| (1 + a_S)^-(m-d)
                        / (prod_i a_i * prod_{j=1..d} (m - j))
where a_S is the sum of a_i over i in S.  The value is m = d + 1; the second
moment is m = 2d + 2.
"""

from __future__ import annotations

import itertools

import numpy as np


def _power_integral(a: np.ndarray, m: int) -> np.ndarray:
    a = np.asarray(a, np.float64)
    d = a.shape[-1]
    total = np.zeros(a.shape[:-1])
    for corner in itertools.product((0, 1), repeat=d):
        s = np.asarray(corner, np.float64)
        total += (-1.0) ** s.sum() * (1.0 + a @ s) ** (-(m - d))
    denom = np.prod(a, axis=-1) * np.prod([m - j for j in range(1, d + 1)])
    return total / denom


def exact(params: dict) -> np.ndarray:
    d = np.asarray(params["a"]).shape[-1]
    return _power_integral(params["a"], d + 1)


def second_moment(params: dict) -> np.ndarray:
    d = np.asarray(params["a"]).shape[-1]
    return _power_integral(params["a"], 2 * d + 2)


def integrand(x, params):
    """f at points ``x`` (n_fn, n, dim); pure ``jax.numpy``."""
    import jax.numpy as jnp
    d = x.shape[-1]
    base = 1 + jnp.sum(x * params["a"][:, None, :], axis=-1, dtype=x.dtype)
    return base ** jnp.asarray(-(d + 1.0), x.dtype)
