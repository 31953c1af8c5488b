"""Structured 3-D finite-volume grid (the computational substrate of the
OpenFOAM case study).

OpenFOAM's HPC_motorbike mesh is unstructured; the paper's systems claims
(directive-per-loop offload, unified memory, pooling) are insensitive to
mesh topology — what costs is cells x iterations x solver structure. We use
a structured grid so the LDU operator re-lays into DIA form (7 shifted
diagonals), which is the TPU-native formulation (no gathers; pure VPU
shifted FMAs). See docs/DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Grid:
    shape: Tuple[int, int, int]          # (nx, ny, nz) cells
    lengths: Tuple[float, float, float] = (1.0, 1.0, 1.0)

    @property
    def n(self) -> int:
        return int(np.prod(self.shape))

    @property
    def h(self) -> Tuple[float, float, float]:
        return tuple(L / s for L, s in zip(self.lengths, self.shape))

    @property
    def vol(self) -> float:
        hx, hy, hz = self.h
        return hx * hy * hz

    def zeros(self):
        return jnp.zeros(self.shape, jnp.float32)

    def field(self, fill: float = 0.0):
        return jnp.full(self.shape, fill, jnp.float32)

    def red_black_masks(self):
        """Two-coloring of the 7-point stencil (for the two-color DILU)."""
        nx, ny, nz = self.shape
        i, j, k = jnp.meshgrid(jnp.arange(nx), jnp.arange(ny), jnp.arange(nz),
                               indexing="ij")
        red = ((i + j + k) % 2 == 0)
        return red, ~red


# face-neighbor shift table: axis, direction
NEIGHBORS = (
    (0, -1), (0, +1),   # -x, +x
    (1, -1), (1, +1),   # -y, +y
    (2, -1), (2, +1),   # -z, +z
)


def shift(f, axis: int, direction: int):
    """Neighbor value with zero padding outside the domain.
    shift(f, 0, -1)[i] == f[i-1] (the -x neighbor)."""
    n = f.shape[axis]
    pad = [(0, 0)] * f.ndim
    if direction < 0:
        pad[axis] = (1, 0)
        sl = [slice(None)] * f.ndim
        sl[axis] = slice(0, n)
        return jnp.pad(f, pad)[tuple(sl)]
    pad[axis] = (0, 1)
    sl = [slice(None)] * f.ndim
    sl[axis] = slice(1, n + 1)
    return jnp.pad(f, pad)[tuple(sl)]


def interior_mask(grid: Grid, axis: int, direction: int):
    """1.0 where the neighbor in (axis, direction) exists.

    A broadcast of one mask along ``axis``: inside a traced region it stays
    an n-element iota, where a grid-sized numpy mask became a literal
    constant that XLA folded every operator built from it into (hundreds
    of MB of program at 256x256x128)."""
    n = grid.shape[axis]
    edge = 0 if direction < 0 else n - 1
    line = (jnp.arange(n) != edge).astype(jnp.float32)
    shape = [1, 1, 1]
    shape[axis] = n
    return jnp.broadcast_to(line.reshape(shape), grid.shape)
