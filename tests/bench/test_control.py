"""The control of each one-chip cell's comparison, at a size a test run can
hold: the plain reference in bfloat16, served in the system's place, comes
out as not correct.  On the chip it runs at the cells' own sizes
(``python3 bench/control.py``)."""

import pytest

from bench import control


@pytest.mark.parametrize("workload,overrides", [
    ("paper_harmonic_d4.closed2", {"request": {"n_fn": 32}}),
    ("genz_corner_vegas_d3.closed4",
     {"request": {"n_fn": 8, "target_stderr": 3e-5}}),
])
def test_bfloat16_control_is_not_correct(workload, overrides):
    rows = control.readings(workload, [5, 6], 1,
                            overrides={"config": overrides})
    ctl = [r for r in rows if r["dtype"] == "bfloat16"]
    twin = [r for r in rows if r["dtype"] == "float32"]
    assert ctl and all(not r["correct"] for r in ctl)
    # the same estimator in float32 reads far lower
    for c, t in zip(ctl, twin):
        assert t["numbers"]["chi2_excess"] * 3 < c["numbers"]["chi2_excess"]
        assert t["numbers"]["missing"] == 0
