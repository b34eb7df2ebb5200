"""The one import point for ``jax.shard_map`` (non-Pallas surface).

Every module that maps a function over a mesh imports :func:`shard_map`
from here, so one place decides how the varying-axes check is set.
(Pallas imports live in ``repro.kernels.pallas_compat`` — the kernel
layer's single import point.)
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the varying-axes check as an argument.

    Pass ``check_vma=False`` around a ``pallas_call``: its ``out_shape``
    carries no varying-axes type, which the check refuses.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
