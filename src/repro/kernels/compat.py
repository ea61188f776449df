"""Pallas TPU compiler params, the interpret-mode rule and the platform gate.

:func:`default_interpret` is the one place that decides how a Pallas
kernel runs: natively on a TPU backend, through the interpreter on every
other backend.  No environment variable or option overrides it.

:func:`pallas_supported` is the serve engine's capability gate (rung 2
of the fallback ladder in docs/kernel_variants.md): the ``pallas``
decode/prefill variants are only registered on the VPE axes when a
trivial pallas_call actually executes on this process's backend.  A
probe that raises is an error, never a demotion — a Mosaic failure on
the chip must stop the program, not quietly serve the gather path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams

_PALLAS_OK: Optional[bool] = None


def default_interpret() -> bool:
    """Interpret mode everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def pallas_supported() -> bool:
    """True when a trivial Pallas kernel computes the right answer here
    (probe result is cached).  Exceptions from the probe propagate."""
    global _PALLAS_OK
    if _PALLAS_OK is None:
        def _probe(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1.0

        out = pl.pallas_call(
            _probe,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=default_interpret(),
        )(jnp.zeros((8, 128), jnp.float32))
        _PALLAS_OK = bool(out[0, 0] == 1.0)
    return _PALLAS_OK
