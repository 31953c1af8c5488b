"""The one ``pallas_call`` every kernel package goes through.

Whether a kernel runs compiled or in the Pallas interpreter is decided by
the platform the surrounding computation is *lowered* for, never at import
and never by ``jax.default_backend()``: the same jitted region can be
compiled for the TPU (Mosaic kernel) and, on the same host, for the CPU
device that ``HostPolicy`` routes to (interpreted kernel).
``jax.lax.platform_dependent`` stages both branches and keeps only the one
for the lowering platform, so the compiler never sees the other.
"""
from __future__ import annotations

from typing import Callable

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel: Callable, **kwargs) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)``: Mosaic-compiled when lowered
    for a TPU, interpreted on every other platform."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)
    return call
