"""The paper's harmonic batch (arXiv:2104.10073, Fig. 1) with drawn
parameters: a, b ~ U(lo, hi) per function, and k per function and axis at
(n + 50) / (2 pi) for n drawn uniformly from 1..n_max."""

from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, request: dict) -> dict:
    n, dim = int(request["n_fn"]), int(request["dim"])
    lo, hi = request["coef_range"]
    a = rng.uniform(lo, hi, n).astype(np.float32)
    b = rng.uniform(lo, hi, n).astype(np.float32)
    idx = rng.integers(1, int(request["k_index_max"]) + 1, (n, dim))
    k = ((idx + 50.0) / (2.0 * np.pi)).astype(np.float32)
    return {"a": a, "b": b, "k": k}


def family(params: dict, request: dict):
    from repro.core import harmonic_family
    return harmonic_family(int(request["n_fn"]), int(request["dim"]),
                           a=params["a"], b=params["b"], k=params["k"])
