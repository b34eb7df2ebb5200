"""``chip_smoke.py`` on the CPU: its checks pass at a tiny size with
interpret-mode kernels, and its entry point refuses to report a result
without a TPU."""

import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(paper_fns=16, round_samples=2048, rounds=1,
                        form_fns=2, sweep_points=2, adaptive_target=1.0,
                        check_fns=1)


def test_one_chip_phase_checks_pass_at_tiny_size(capsys):
    chip_smoke.one_chip(TINY)
    out = capsys.readouterr().out
    assert "warm resubmit of the paper batch: 0 launches" in out
    assert "fused vs chunked on the same counters" in out


def test_four_chip_phase_checks_pass_on_four_host_devices():
    # a child process: the device count is fixed before JAX starts
    prog = ("import chip_smoke, test_chip_smoke\n"
            "chip_smoke.four_chips(test_chip_smoke.TINY)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.dirname(os.path.abspath(__file__))]))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "'n_devices': 4" in out.stdout
    assert "mesh vs one device" in out.stdout


def test_check_f32_rejects_different_counters():
    # a one-stderr difference is what different samples would give
    try:
        chip_smoke.check_f32("x", [1.0], [0.0], [1.0])
    except chip_smoke.SmokeFailure:
        return
    raise AssertionError("a one-stderr difference passed the f32 check")


def test_entry_point_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert '"ok"' not in out.stdout
