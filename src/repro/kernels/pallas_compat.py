"""Single import point for Pallas across the kernel subsystem.

Every kernel module imports ``pl``/``pltpu`` from here — never from
``jax.experimental`` directly — so a JAX API move is absorbed exactly
once.

Interpret mode: real Mosaic lowering only exists on TPU.
:func:`should_interpret` is the one place that decides when kernels run
under the Pallas interpreter (the CPU test runs) vs compiled; ops-layer
wrappers default their ``interpret`` argument from it.  On a TPU
backend nothing picks interpret mode.

If a future jax moves ``pl``/``pltpu`` themselves, only this module
changes.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl  # noqa: F401  (re-export)
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (re-export)


def should_interpret() -> bool:
    """True when pallas_call must run interpreted (no Mosaic backend)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Ops-layer helper: explicit flag wins, else backend autodetect."""
    return should_interpret() if interpret is None else bool(interpret)
