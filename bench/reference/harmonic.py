"""The paper's harmonic integrand f(x) = a cos(k.x) + b sin(k.x) over
[0,1]^d, in closed form.

With z(k) = prod_d (e^{i k_d} - 1) / (i k_d) = int e^{i k.x} dx:
  int f      = a Re z(k) + b Im z(k)
  int f^2    = (a^2 + b^2)/2 + (a^2 - b^2)/2 Re z(2k) + a b Im z(2k)
since f^2 = (a^2+b^2)/2 + (a^2-b^2)/2 cos 2k.x + a b sin 2k.x.
"""

from __future__ import annotations

import numpy as np


def _z(k: np.ndarray) -> np.ndarray:
    k = np.asarray(k, np.float64)
    return np.prod((np.exp(1j * k) - 1.0) / (1j * k), axis=-1)


def exact(params: dict) -> np.ndarray:
    a = np.asarray(params["a"], np.float64)
    b = np.asarray(params["b"], np.float64)
    z = _z(params["k"])
    return a * z.real + b * z.imag


def second_moment(params: dict) -> np.ndarray:
    a = np.asarray(params["a"], np.float64)
    b = np.asarray(params["b"], np.float64)
    z2 = _z(2.0 * np.asarray(params["k"], np.float64))
    return (a * a + b * b) / 2 + (a * a - b * b) / 2 * z2.real + a * b * z2.imag


def integrand(x, params):
    """f at points ``x`` (..., n_fn, n, dim); ``params`` leaves (n_fn, ...)
    in the dtype the caller computes in.  Pure ``jax.numpy``."""
    import jax.numpy as jnp
    phase = jnp.sum(x * params["k"][:, None, :], axis=-1, dtype=x.dtype)
    return (params["a"][:, None] * jnp.cos(phase)
            + params["b"][:, None] * jnp.sin(phase))
