"""Multi-host launcher helpers (single-process semantics)."""

import os

import pytest

from repro.launch import multihost


def test_initialize_noop_without_env(monkeypatch):
    for var in ("REPRO_COORD", "REPRO_NUM_PROCS", "REPRO_PROC_ID",
                "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    assert multihost.initialize_if_needed(verbose=False) is False


def test_initialize_raises_when_env_names_several_hosts(monkeypatch):
    import jax

    def refuse(**kwargs):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    for var in ("TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("REPRO_COORD", "localhost:1")
    monkeypatch.setenv("REPRO_NUM_PROCS", "2")
    monkeypatch.setenv("REPRO_PROC_ID", "1")
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        multihost.initialize_if_needed(verbose=False)


def test_initialize_noop_for_one_named_worker(monkeypatch):
    for var in ("REPRO_COORD", "REPRO_NUM_PROCS", "REPRO_PROC_ID",
                "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert multihost.initialize_if_needed(verbose=False) is False


def test_host_batch_rows_single_process():
    s = multihost.host_batch_rows(256)
    assert (s.start, s.stop) == (0, 256)


def test_scripts_exist_and_are_executable_shell():
    base = os.path.join(os.path.dirname(multihost.__file__), "scripts")
    for name in ("train_pod.sh", "integrate_pod.sh"):
        path = os.path.join(base, name)
        assert os.path.exists(path), path
        head = open(path).readline()
        assert head.startswith("#!"), path
