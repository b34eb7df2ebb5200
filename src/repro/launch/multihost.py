"""Multi-host initialisation for real TPU pods.

On a v5e pod each host sees 4 chips; `jax.distributed.initialize` welds the
hosts into one runtime so `jax.devices()` returns all 256 (or 512) chips
and `make_production_mesh()` works unchanged.  This module reads the
standard TPU/GKE environment (or explicit flags) and must be imported
before any other jax usage by the pod entrypoints
(`launch/scripts/*.sh`).

Supported environments:
  * Cloud TPU VMs / GKE: coordinator + process id from the TPU metadata
    (jax.distributed.initialize() with no args autodetects) when
    TPU_WORKER_HOSTNAMES names several hosts or
    MEGASCALE_COORDINATOR_ADDRESS is set.
  * Generic MPI-ish: REPRO_COORD, REPRO_NUM_PROCS, REPRO_PROC_ID env vars.

Elastic note: on restart with a different number of hosts, initialise with
the new topology and call `repro.distributed.elastic.elastic_restore` —
checkpoints are mesh-independent (full arrays + logical re-derivation).
"""

from __future__ import annotations

import os


def initialize_if_needed(verbose: bool = True) -> bool:
    """Initialise jax.distributed from the environment. Returns True if a
    multi-host runtime was set up, False for single-process runs.

    When the environment names several hosts, a failed initialisation
    raises: carrying on as one host would run a different job.
    """
    coord = os.environ.get("REPRO_COORD")
    nprocs = os.environ.get("REPRO_NUM_PROCS")
    pid = os.environ.get("REPRO_PROC_ID")
    workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if coord and nprocs and pid:
        kwargs = {"coordinator_address": coord,
                  "num_processes": int(nprocs), "process_id": int(pid)}
    elif "," in workers or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        kwargs = {}                        # TPU metadata autodetect
    else:
        return False
    import jax
    jax.distributed.initialize(**kwargs)
    if verbose:
        print(f"[multihost] process {jax.process_index()}/"
              f"{jax.process_count()}: {jax.local_device_count()} local / "
              f"{jax.device_count()} global devices")
    return True


def host_batch_rows(global_batch: int) -> "slice":
    """The rows of the global batch this host should materialise
    (feeds TokenStream.next_batch(rows=...))."""
    import jax
    per = global_batch // jax.process_count()
    start = jax.process_index() * per
    return slice(start, start + per)
