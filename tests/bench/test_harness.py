"""A whole run of a cell, at a small size on the CPU: the result line, the
refusals, and the per-layer readers on what a traced run collects."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import discover

ROOT = str(discover.ROOT)


def test_paper_cell_result_line(run_cell):
    rc, line, err = run_cell("paper_harmonic_d4.closed2", seed=2**31 + 5)
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"fn_samples_per_s", "setup_s"}
    assert line["metrics"]["fn_samples_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["check"]) == {"missing", "n_mismatch", "chi2_excess"}
    assert err.strip().splitlines()[-1].startswith("check chi2_excess ")


def test_vegas_cell_traced_reports_its_layers(run_cell):
    rc, line, _ = run_cell("genz_corner_vegas_d3.closed4", trace=True)
    assert rc == 0 and line["correct"] is True
    m = line["metrics"]
    # the CPU has no device plane: the device's metrics are left out
    assert {"host_ms_per_wave.vegas", "plan_ms_per_wave",
            "samples_per_result", "wal_commit_ms_per_wave",
            "compiles_in_window.vegas"} <= set(m)
    assert "device_idle_share.vegas" not in m
    assert m["compiles_in_window.vegas"]["value"] == 0
    assert m["wal_commit_ms_per_wave"]["value"] > 0
    assert set(line["check"]) == {"missing", "stderr_over_target",
                                  "chi2_excess", "unjournaled", "unsynced"}
    assert line["check"]["stderr_over_target"]["value"] <= 1.0
    assert line["check"]["unjournaled"]["value"] == 0
    assert line["check"]["unsynced"]["value"] == 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper_harmonic_d4.closed2", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in discover.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d)
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("bad", ["--seed=-1", "--seconds=0"])
def test_command_rejects_bad_arguments(bad):
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "x", "--seed", "1",
         "--seconds", "1", bad], cwd=ROOT, capture_output=True, text=True,
        timeout=60)
    assert p.returncode == 2 and p.stdout == ""
