"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Processes that need many devices set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before any jax
import — 512 for the dry-run sweep, the APU count for the multi-APU
scaling driver (``repro.launch.scaling``, see docs/SCALING.md); smoke
tests and in-process benchmarks see the real single device.

Mesh topology (TPU v5e pods):
  single-pod : (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
The "pod" axis carries the slowest links (DCN/optical); FSDP/DP gradient
reduction over ("pod","data") is therefore hierarchical by construction.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, what: str, hint: str):
    """The one mesh constructor: the first ``prod(shape)`` devices, every
    axis ``Auto`` so the model's ``with_sharding_constraint`` calls
    (``launch/sharding.py``) may name any mesh axis."""
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices for {what} {shape}, have "
                           f"{len(devices)}; {hint}")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes, "mesh", "the dry-run sets "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=512")


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over the first prod(shape) devices (default 1x1 over the
    single real device — sharding unit tests).  ``serve --mesh N`` builds
    an (N, 1) smoke mesh over the simulated APUs so the model's internal
    sharding constraints share a device assignment with the APU mesh."""
    n = 1
    for s in shape:
        n *= s
    return _mesh(shape, axes, "smoke mesh",
                 f"set XLA_FLAGS={apu_flags(n)} before importing jax")


def apu_flags(n_apus: int) -> str:
    """The XLA flag that simulates an ``n_apus``-APU node on a CPU host.
    Must be in ``XLA_FLAGS`` *before* the first jax import (subprocess
    drivers like ``repro.launch.scaling`` set it; shells export it)."""
    return f"--xla_force_host_platform_device_count={n_apus}"


def near_square_mesh_shape(n: int) -> tuple:
    """Near-square 2-D factorization of an APU count: largest divisor
    ``d <= sqrt(n)`` gives ``(d, n // d)`` — 4 -> (2, 2), 8 -> (2, 4),
    6 -> (2, 3) — which cuts halo surface-to-volume versus a 1-D slab
    decomposition (docs/SCALING.md).  Primes (and 1) stay 1-D: ``(n,)``.
    Shared by ``fig_scaling`` and the policy autotuner's mesh-shape axis
    (``repro.tune``, docs/AUTOTUNE.md)."""
    n = int(n)
    if n < 1:
        raise ValueError(f"APU count must be >= 1, got {n}")
    best = 1
    for d in range(2, int(n ** 0.5) + 1):
        if n % d == 0:
            best = d
    return (best, n // best) if best > 1 else (n,)


def parse_mesh_shape(spec) -> tuple:
    """Parse a mesh-shape spec: ``4`` / ``"4"`` -> ``(4,)`` (1-D),
    ``"2x2"`` -> ``(2, 2)``, ``"2x2x2"`` -> ``(2, 2, 2)``.  The CLI
    surface of the 2-D/3-D domain decomposition (``launch.scaling
    --mesh``, ``FIG_SCALING_MESH``)."""
    if isinstance(spec, int):
        return (spec,)
    if isinstance(spec, (tuple, list)):
        return tuple(int(s) for s in spec)
    shape = tuple(int(s) for s in str(spec).lower().split("x") if s)
    if not shape or any(s < 1 for s in shape):
        raise ValueError(f"bad mesh shape {spec!r}: want e.g. '4' or '2x2'")
    return shape


def make_apu_mesh(n_apus=1, axis: str = "apu"):
    """Mesh of simulated APUs — the node topology of the multi-APU replay
    (``repro.core.shard_program``).  Each "APU" is one forced
    host-platform device; the Infinity Fabric between them is the
    inter-device transfer path XLA partitions collectives onto.

    ``n_apus`` is an APU count (1-D mesh, axis ``"apu"`` — the PR-3
    surface) or a mesh shape (``(2, 2)`` / ``"2x2"``): an N-D
    decomposition with axes ``("apu0", "apu1", ...)`` that cuts
    surface-to-volume (docs/SCALING.md)."""
    shape = parse_mesh_shape(n_apus)
    axes = (axis,) if len(shape) == 1 else tuple(
        f"{axis}{i}" for i in range(len(shape)))
    n = 1
    for s in shape:
        n *= s
    return _mesh(shape, axes, "an APU mesh",
                 f"set XLA_FLAGS={apu_flags(n)} before importing jax "
                 "(see docs/SCALING.md)")
