"""A run with the timed path broken underneath comes out not correct, for
each fault that a cell of one chip can have, and for a durable store that
breaks its promise."""

import pytest

from conftest import (answer_altered, half_left_out, plant,
                      samples_halved)

PAPER = "paper_harmonic_d4.closed2"
VEGAS = "genz_corner_vegas_d3.closed4"


@pytest.mark.parametrize("workload,fault,caught_by", [
    (PAPER, "unchanged", "missing"),
    (PAPER, half_left_out, "chi2_excess"),
    (PAPER, samples_halved, "n_mismatch"),
    (PAPER, answer_altered, "chi2_excess"),
    (VEGAS, "unchanged", "missing"),
    (VEGAS, half_left_out, "chi2_excess"),
    (VEGAS, answer_altered, "chi2_excess"),
    (VEGAS, "unjournaled", "unjournaled"),
    (VEGAS, "unsynced", "unsynced"),
], ids=["paper-unchanged", "paper-half-batch", "paper-half-samples",
        "paper-altered", "vegas-unchanged", "vegas-half-batch",
        "vegas-altered", "vegas-unjournaled", "vegas-unsynced"])
def test_fault_makes_the_run_incorrect(run_cell, monkeypatch, workload,
                                       fault, caught_by):
    plant(monkeypatch, fault)
    rc, line, _ = run_cell(workload, seed=777, late_s=2.0)
    assert rc == 0
    assert line["correct"] is False
    got = line["check"][caught_by]
    # a number that is not finite is printed as text
    assert isinstance(got["value"], str) or got["value"] > got["limit"], \
        line["check"]
