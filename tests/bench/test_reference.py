"""The closed forms the benchmark compares with, against float64
tensor-product Gauss-Legendre quadrature, and the plain estimator against
the closed forms."""

import itertools

import numpy as np
import pytest

from bench import discover
from bench.harness import Cell


def _quad(f, dim: int, n: int) -> float:
    x, w = np.polynomial.legendre.leggauss(n)
    x, w = (x + 1) / 2, w / 2
    grids = np.meshgrid(*([x] * dim), indexing="ij")
    pts = np.stack([g.ravel() for g in grids], axis=-1)
    wts = np.prod(np.meshgrid(*([w] * dim), indexing="ij"), axis=0).ravel()
    return float(np.sum(f(pts) * wts))


@pytest.mark.parametrize("dim", [1, 2, 3])
def test_harmonic_closed_form_matches_quadrature(dim):
    ref = discover.reference("harmonic")
    rng = np.random.default_rng(dim)
    a, b = rng.uniform(0.5, 1.5, 2)
    k = (rng.integers(1, 60, dim) + 50) / (2 * np.pi)
    p = {"a": np.array([a]), "b": np.array([b]), "k": k[None, :]}

    def f(x):
        ph = x @ k
        return a * np.cos(ph) + b * np.sin(ph)

    n = {1: 200, 2: 120, 3: 60}[dim]
    assert ref.exact(p)[0] == pytest.approx(_quad(f, dim, n), abs=1e-12)
    assert ref.second_moment(p)[0] == pytest.approx(
        _quad(lambda x: f(x) ** 2, dim, n), abs=1e-12)


@pytest.mark.parametrize("dim", [1, 2, 3])
def test_corner_peak_closed_form_matches_quadrature(dim):
    ref = discover.reference("genz_corner")
    rng = np.random.default_rng(10 + dim)
    a = rng.uniform(0.1, 1.1, dim)
    a *= 1.85 / a.sum()
    p = {"a": a[None, :]}

    def f(x):
        return (1 + x @ a) ** (-(dim + 1.0))

    assert ref.exact(p)[0] == pytest.approx(_quad(f, dim, 40), rel=1e-12)
    assert ref.second_moment(p)[0] == pytest.approx(
        _quad(lambda x: f(x) ** 2, dim, 40), rel=1e-12)


@pytest.mark.parametrize("form", ["harmonic", "genz_corner"])
def test_forms_draw_what_the_closed_form_reads(form):
    """Drawn parameters are float32, one row per function, and give finite
    values and positive variances."""
    name = {"harmonic": "paper_harmonic_d4",
            "genz_corner": "genz_corner_vegas_d3"}[form]
    cell = Cell(discover.config(name), seed=3)
    p = cell.draw(0, 0, 0)
    ref = discover.reference(form)
    n = cell.request["n_fn"]
    assert all(v.dtype == np.float32 and len(v) == n for v in p.values())
    ex = ref.exact(p)
    var = ref.second_moment(p) - ex * ex
    assert ex.shape == (n,) and np.all(np.isfinite(ex)) and np.all(var > 0)


def test_plain_estimator_agrees_with_closed_form_in_float32():
    import jax.numpy as jnp

    from bench.reference import mc
    ref = discover.reference("genz_corner")
    a = np.asarray([[0.5, 0.8, 0.55], [0.2, 0.9, 0.75]], np.float32)
    p = {"a": a}
    means, ses, n = mc.plain_mc(ref.integrand, p, 3, 1 << 17, jnp.float32, 7)
    assert n == 1 << 17
    pulls = np.abs(means - ref.exact(p)) / ses
    assert np.all(pulls < 5)


def test_reference_modules_import_nothing_of_the_system():
    root = discover.BENCH / "reference"
    for path in itertools.chain(root.glob("*.py")):
        text = path.read_text()
        assert "repro" not in text, path
