"""Unified-memory abstraction (paper C1): one logical space, placement by
policy.

On MI300A the hardware gives a single physical memory; any pointer is valid
on CPU cores and GPU CUs. On TPU the analogue is JAX *memory kinds*: every
array lives in ``device`` (HBM) or ``pinned_host``/``unpinned_host`` (DRAM),
addressable by the same program, with XLA streaming data between spaces when
compute needs it. This module gives the rest of the framework a single
placement API so application code never hard-codes a memory space — the
paper's "no programming distinction between host and device memory" (§3).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Any, Callable, Optional

import jax


class MemSpace(enum.Enum):
    DEVICE = "device"            # HBM
    HOST = "pinned_host"         # DMA-able host DRAM
    HOST_UNPINNED = "unpinned_host"

    @property
    def kind(self) -> str:
        return self.value


_SPACES_CACHE: dict = {}


def supported_spaces(device=None) -> set:
    d = device or jax.devices()[0]
    if d not in _SPACES_CACHE:
        try:
            _SPACES_CACHE[d] = {m.kind for m in d.addressable_memories()}
        except Exception:                   # pragma: no cover
            _SPACES_CACHE[d] = {"device"}
    return _SPACES_CACHE[d]


def preferred_host_space(device=None) -> Optional[MemSpace]:
    """Best available host-DRAM space: pinned if the platform has it,
    unpinned otherwise, None when the device exposes no host space at all."""
    sup = supported_spaces(device)
    for space in (MemSpace.HOST, MemSpace.HOST_UNPINNED):
        if space.kind in sup:
            return space
    return None


def place(x, space: MemSpace, device=None):
    """Move one array to a memory space (no-op if already there or if the
    platform does not expose that space).

    A sharded array (NamedSharding etc.) keeps its partitioning — only the
    memory kind is rebound, so placing FSDP-sharded optimizer moments or a
    mesh-scattered KV cache into host space never gathers onto one device.
    Unsharded inputs land on ``device`` (default: the first device)."""
    d = device or jax.devices()[0]
    if space.kind not in supported_spaces(d):
        return x
    sh = None
    cur = getattr(x, "sharding", None)
    if cur is not None and \
            not isinstance(cur, jax.sharding.SingleDeviceSharding):
        try:
            sh = cur.with_memory_kind(space.kind)
        except Exception:               # shardings without memory kinds
            sh = None
    if sh is None:
        sh = jax.sharding.SingleDeviceSharding(d, memory_kind=space.kind)
    return jax.device_put(x, sh)


def tree_place(tree, space: MemSpace, device=None, min_bytes: int = 0):
    """Place every array leaf of a pytree into a memory space.

    ``min_bytes`` is a placement threshold (paper C4's "pool only buffers
    above 5K elements", applied to placement): leaves smaller than it stay
    where they are — moving a scalar across spaces costs more than it saves.
    """
    def maybe(x):
        # leaves without .nbytes (Python scalars) count as size 0: with a
        # threshold set they stay put rather than becoming committed Arrays
        if min_bytes and getattr(x, "nbytes", 0) < min_bytes:
            return x
        return place(x, space, device)
    return jax.tree.map(maybe, tree)


def device_operands(fn: Callable) -> Callable:
    """Wrap ``fn`` (to be jitted) so that, inside the trace, every array
    operand typed as host memory is first moved into device memory.  A
    jitted computation may take ``pinned_host`` operands, but the TPU
    compiler refuses to compute on them in place, so a region fetches its
    host-placed operands at entry; the copy is part of the program."""
    def fetch(x):
        if isinstance(x, jax.Array) and getattr(
                jax.typeof(x), "memory_space", None) == jax.memory.Space.Host:
            return jax.device_put(x, jax.memory.Space.Device)
        return x

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        args, kwargs = jax.tree.map(fetch, (args, kwargs))
        return fn(*args, **kwargs)
    return wrapped


def tree_place_budgeted(tree, budget, device=None, min_bytes: int = 0,
                        device_space: MemSpace = MemSpace.DEVICE,
                        spill_space: Optional[MemSpace] = None,
                        charge: bool = True):
    """Place leaves into ``device_space`` while ``budget`` (a
    :class:`~repro.core.oversub.MemoryBudget`, duck-typed ``admit``/
    ``consult``) has headroom; leaves beyond it land in ``spill_space``
    (the platform's preferred host DRAM space by default) instead of
    failing — the oversubscription model: exceeding device capacity
    degrades placement, never correctness.  ``charge=True`` accounts
    admitted leaves as device-resident (``budget.admit``; the caller
    releases them); ``charge=False`` only consults — the advisory form
    used for per-call placement hints.  Leaf order is deterministic
    (``jax.tree.map`` order), so the same tree under the same budget
    always splits the same way."""
    spill = spill_space or preferred_host_space(device) or device_space

    def maybe(x):
        nbytes = getattr(x, "nbytes", 0)
        if min_bytes and nbytes < min_bytes:
            return x
        ok = budget.admit(nbytes) if charge else budget.consult(nbytes)
        return place(x, device_space if ok else spill, device)
    return jax.tree.map(maybe, tree)


def place_like(tree, shardings):
    """device_put each leaf onto its matching sharding — the placement
    companion to :func:`tree_place` for sharded programs.  ``shardings``
    must mirror ``tree`` leaf-for-leaf (NamedShardings /
    SingleDeviceShardings carrying memory kinds)."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


def shard_along(mesh, axis_name: str, ndim: int, dim: int):
    """NamedSharding splitting array dimension ``dim`` (negative indices
    allowed) of an ``ndim``-rank array over mesh axis ``axis_name``, all
    other dimensions replicated — the one-axis domain decomposition of the
    multi-APU replay (``repro.core.shard_program``)."""
    dim = dim % ndim if ndim else 0
    spec = [None] * ndim
    if ndim:
        spec[dim] = axis_name
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec))


def shard_along_nd(mesh, assignments, ndim: int):
    """NamedSharding splitting several array dimensions at once:
    ``assignments`` maps array dimension (normalized, ``0 <= dim < ndim``)
    to mesh axis name — the N-D domain decomposition of the multi-APU
    replay (2-D/3-D meshes cut surface-to-volume, docs/SCALING.md).
    Unassigned dimensions replicate."""
    spec = [None] * ndim
    for dim, axis_name in dict(assignments).items():
        spec[dim % ndim if ndim else 0] = axis_name
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec))


def replicated_sharding(mesh):
    """NamedSharding replicating an array across every mesh device."""
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def space_of(x) -> Optional[str]:
    try:
        return x.sharding.memory_kind
    except Exception:
        return None


def with_memory_kind(sharding: jax.sharding.Sharding, space: MemSpace):
    """Rebind a NamedSharding to a memory kind (for jit in/out_shardings)."""
    return sharding.with_memory_kind(space.kind)


@dataclasses.dataclass
class UnifiedArena:
    """Two named spaces over the unified address map. The *discrete-memory
    emulation* (benchmarks, Fig 6) stages data between the two with real
    copies; the *unified* executor never calls :meth:`to_device`/:meth:`to_host`
    — that asymmetry is the paper's measured effect."""
    device: Any = None
    host_space: MemSpace = MemSpace.HOST
    device_space: MemSpace = MemSpace.DEVICE

    def __post_init__(self):
        self.device = self.device or jax.devices()[0]
        sup = supported_spaces(self.device)
        if self.host_space.kind not in sup:
            # degrade gracefully: pinned -> unpinned host -> device space
            self.host_space = preferred_host_space(self.device) \
                or self.device_space

    def to_device(self, tree):
        return tree_place(tree, self.device_space, self.device)

    def to_host(self, tree):
        return tree_place(tree, self.host_space, self.device)

    def bytes_of(self, tree) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(tree)
                   if hasattr(x, "nbytes"))
