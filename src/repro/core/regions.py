"""One region, one policy: the canonical offload API (paper C1+C2+C3).

The paper's central claim is that unified memory lets a *single* abstraction
— "a region with a directive" — be retargeted across host, discrete-managed,
and APU execution without touching application code.  This module is that
abstraction:

* :class:`Region` — one OpenMP-directive-sized unit of work: the function,
  its per-target compiled executables, a problem-size measure (the ``n`` of
  ``if(target: n > TARGET_CUT_OFF)``), the offload hint, and optional
  :class:`~repro.core.umem.MemSpace` placement hints per argument / result.

* :class:`ExecutionPolicy` — four orthogonal, composable axes:

  - **placement** (:class:`Placer`): where operands/results nominally live,
    expressed as ``MemSpace`` hints applied through ``umem`` (paper C1);
  - **routing** (:class:`Router`): which executable runs this call — the
    static host/device choice of the three §5 execution modes, or the
    size-based ``TARGET_CUT_OFF`` clause of the retired dispatch shim
    (paper C3, listings 4-6);
  - **staging** (:class:`Stager`): what crossing the host/device boundary
    costs — nothing on an APU, real out-of-place copies through pooled
    buffers on a managed-memory dGPU (paper §5 Fig 6, C4);
  - **selection** (:class:`Selector`): which *implementation variant* of
    the region runs — OpenMP 5.2's ``declare variant`` / ``metadirective``
    dispatch.  A region registers named variants (``ref`` is always the
    decorated function; custom kernels register as e.g. ``pallas``) and
    the policy picks one per call: :class:`StaticSelector` (one name
    everywhere, base-function fallback), :class:`TargetSelector`
    (``match(device)``-style target-conditioned defaults), or
    :class:`AutotuneSelector` (calibrated winners per region x target x
    size-bucket, persisted in the ledger like ``TARGET_CUT_OFF``).

* :class:`Executor` — runs Regions under a policy and accounts every call
  (where it ran, what it cost, how many elements were routed which way)
  into one :class:`~repro.core.ledger.Ledger`, so routing decisions and
  staging fractions appear in the same ``coverage_report()``.

The old ``UnifiedExecutor`` / ``DiscreteExecutor`` / ``HostExecutor``
classes and ``TargetDispatch`` are RETIRED: the pre-regions ``executors``
and ``dispatch`` modules are deprecation-alias stubs for external callers
only, and nothing inside the repo imports them (CI gates it via
``tools/check_retired_imports.py``).
"""
from __future__ import annotations

import dataclasses
import inspect
import time
import weakref
from typing import (Any, Callable, Dict, Mapping, Optional, Protocol,
                    Sequence, Tuple, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import umem
from repro.core.ledger import GLOBAL_LEDGER, Ledger
from repro.core.pool import DeviceBufferPool, HostStagingPool
from repro.core.umem import MemSpace, UnifiedArena

DEFAULT_CUTOFF = 16384          # the paper's empirical TARGET_CUT_OFF

#: routing targets an executable can be compiled for
TARGETS = ("default", "host", "device")


def host_device():
    return jax.devices("cpu")[0]


def accel_device():
    accel = [d for d in jax.devices() if d.platform != "cpu"]
    return accel[0] if accel else jax.devices()[0]


def _to_host(tree):
    """Commit every array operand of ``tree`` to the host CPU device: a
    jitted call runs where its committed operands live, so routing a call
    to ``host`` means moving them there."""
    dev = host_device()
    sh = jax.sharding.SingleDeviceSharding(dev)

    def move(x):
        if isinstance(x, jax.Array) and x.devices() != {dev}:
            return jax.device_put(x, sh)
        return x
    return jax.tree.map(move, tree)


def _host_placed(tree) -> bool:
    """Does any array leaf of ``tree`` live in a host memory space?"""
    return any(umem.space_of(x) in (MemSpace.HOST.kind,
                                    MemSpace.HOST_UNPINNED.kind)
               for x in jax.tree.leaves(tree) if isinstance(x, jax.Array))


def _param_indices(fn: Callable) -> Dict[str, int]:
    """Positional index of each named parameter, so placement hints keyed
    by name apply to positionally-passed arguments too."""
    try:
        import inspect
        return {name: i for i, name
                in enumerate(inspect.signature(fn).parameters)}
    except (ValueError, TypeError):         # builtins, odd callables
        return {}


def default_size(args, kwargs) -> int:
    """Problem size of a call = size of the LARGEST array leaf.

    The largest leaf, not the first: a small scalar leading argument (an
    ``alpha``, a tolerance) must not force host routing for a call whose
    field operands are millions of cells."""
    sizes = [int(a.size) for a in jax.tree.leaves((args, kwargs))
             if hasattr(a, "size")]
    return max(sizes, default=0)


# ---------------------------------------------------------------------------
# Region
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)        # identity semantics: regions are
class Region:                           # hashable, usable as dict/set keys
    """One directive-sized region: fn + compiled executables + hints.

    ``arg_spaces`` maps positional index or keyword name to a
    :class:`MemSpace` placement hint; ``result_space`` hints where results
    should land — either one :class:`MemSpace` for the whole result, or a
    mapping from top-level tuple index / dict key to a space so a region
    returning ``(params, opt_state, gnorm)`` can pin just ``opt_state``
    host-side.  Hints are *advisory*: the executing policy's placement
    axis decides whether (and above what byte threshold) to honor them.

    ``stencil`` declares the region's neighbor-access pattern as a sequence
    of ``(grid_axis, offset)`` pairs (the DIA offset table of
    ``repro.cfd.dia`` is the canonical source).  Pointwise regions leave it
    ``None``.  Sharded replay (``repro.core.shard_program``) reads it to
    infer the halo width a domain decomposition must exchange before the
    region runs; single-device executors ignore it entirely.  ``halo_args``
    optionally narrows the exchange to the top-level arguments (positions
    or parameter names) whose *neighbors* the stencil actually reads —
    coefficient stacks multiply locally and need no halo.

    ``donate_args`` lists positional arguments donated to XLA
    (``jax.jit(donate_argnums=...)``): the output may alias the input's
    storage instead of copying — how a pass-through region (serve's
    ``KV_APPEND`` cache commit) stays O(1) instead of O(bytes).  Donate
    only when the region is the LAST consumer of that argument everywhere
    it appears (capture executes eagerly and deletes donated buffers
    too).  Executors running under a staging policy automatically fall
    back to non-donating executables (``executable(donate=False)``):
    staged operands can alias pooled pages whose lifetime the stager
    manages, and donation must never hand pool-owned storage to XLA.
    """
    name: str
    fn: Callable
    offloaded: bool = True
    size_fn: Callable = default_size
    arg_spaces: Optional[Mapping[Any, MemSpace]] = None
    result_space: Any = None      # MemSpace | {tuple index / dict key: MemSpace}
    stencil: Optional[Sequence[Tuple[int, int]]] = None
    halo_args: Optional[Sequence[Any]] = None
    donate_args: Optional[Sequence[int]] = None
    ledger: Ledger = dataclasses.field(default_factory=lambda: GLOBAL_LEDGER)

    def stencil_width(self, axis: int) -> int:
        """Halo reach of this region's declared ``stencil`` along grid
        ``axis``: the maximum |offset| of any band on that axis, 0 for
        pointwise regions.  A width-``w`` stencil applied ``k`` times
        reaches ``k*w`` (``repro.cfd.dia.compose_offsets`` composes the
        declared tables), which is exactly the ghost-zone depth the
        wide-halo exchange schedule provisions (docs/SCALING.md)."""
        if not self.stencil:
            return 0
        return max((abs(d) for ax, d in self.stencil if ax == axis),
                   default=0)

    def __post_init__(self):
        if self.size_fn is None:
            self.size_fn = default_size
        self.name = self.ledger.register(self.name, self.offloaded)
        # __name__ stays a valid identifier (regions may be named "grad(p)")
        self.__name__ = getattr(self.fn, "__name__", "region")
        self.__qualname__ = self.__name__
        self._jitted = None
        #: named implementations (OpenMP declare variant): "ref" is ALWAYS
        #: the decorated function itself — the base function every selector
        #: can fall back to
        self._variants: Dict[str, Callable] = {"ref": self.fn}
        self._jvar: Dict[str, Callable] = {}
        self._exec: Dict[Tuple[str, str], Callable] = {}
        self._param_index = _param_indices(self.fn)
        self._validate_donate_args()

    def _validate_donate_args(self) -> None:
        """Fail at declaration, not jit time: donate_args must be
        non-negative positional indices inside the signature (when it is
        introspectable and takes no *args), and must not overlap
        halo_args — a donated buffer is deleted by XLA while the sharded
        halo exchange still needs to read its neighbors."""
        if not self.donate_args:
            return
        bad = [d for d in self.donate_args
               if not isinstance(d, int) or d < 0]
        if bad:
            raise ValueError(
                f"region {self.name!r}: donate_args must be non-negative "
                f"positional indices, got {bad!r}")
        try:
            params = list(inspect.signature(self.fn).parameters.values())
        except (TypeError, ValueError):
            params = None                      # not introspectable: skip
        if params is not None and not any(
                p.kind is inspect.Parameter.VAR_POSITIONAL for p in params):
            n_pos = sum(1 for p in params if p.kind in (
                inspect.Parameter.POSITIONAL_ONLY,
                inspect.Parameter.POSITIONAL_OR_KEYWORD))
            out = [d for d in self.donate_args if d >= n_pos]
            if out:
                raise ValueError(
                    f"region {self.name!r}: donate_args {out} out of range "
                    f"for a function with {n_pos} positional parameters "
                    f"({tuple(self._param_index)})")
        if self.halo_args:
            halo_idx = {h for h in self.halo_args if isinstance(h, int)}
            halo_idx |= {self._param_index[h] for h in self.halo_args
                         if isinstance(h, str) and h in self._param_index}
            clash = sorted(halo_idx & set(self.donate_args))
            if clash:
                raise ValueError(
                    f"region {self.name!r}: donate_args {clash} overlap "
                    f"halo_args {tuple(self.halo_args)}; a donated operand "
                    "is deleted by XLA while the sharded halo exchange "
                    "still reads its ghost cells — donate a different "
                    "argument or drop it from halo_args")

    # -- implementation variants (declare variant) -----------------------
    @property
    def variants(self) -> Tuple[str, ...]:
        """Names of the registered implementation variants."""
        return tuple(self._variants)

    def variant(self, name: str, fn: Optional[Callable] = None):
        """Register a named implementation of this region — the
        ``declare variant`` directive.  Decorator form::

            @region("Amul")
            def amul(diag, off, x): ...          # the "ref" variant

            @amul.variant("pallas")
            def _amul_kernel(diag, off, x): ...  # same signature/semantics

        Variants must accept the same arguments and return the same
        structure as the base function; which one runs is decided per call
        by the executing policy's :class:`Selector`.  Re-registering
        ``"ref"`` replaces the base function itself, so every path —
        jitted executables and the fused ``as_fn`` composite alike — sees
        the same implementation."""
        def register(f: Callable) -> Callable:
            self._variants[name] = f
            if name == "ref":                   # ref IS the base function
                self.fn = f
                self._jitted = None
            for key in [k for k in self._jvar if k[0] == name]:
                del self._jvar[key]             # drop stale compilations
            for key in [k for k in self._exec if k[1] == name]:
                del self._exec[key]
            return f
        return register(fn) if fn is not None else register

    def impl_fn(self, name: str = "ref") -> Callable:
        """The raw (unjitted) callable of one registered variant."""
        try:
            return self._variants[name]
        except KeyError:
            raise KeyError(f"region {self.name!r} has no variant {name!r}; "
                           f"registered: {self.variants}") from None

    def resolve(self, name: str) -> str:
        """Variant-name resolution with the declare-variant fallback: an
        unregistered name dispatches to the base function (``ref``)."""
        return name if name in self._variants else "ref"

    # -- per-(target, variant) compiled executables ----------------------
    def _jit(self, fn: Callable) -> Callable:
        return jax.jit(umem.device_operands(fn),
                       donate_argnums=tuple(self.donate_args or ()))

    @property
    def jitted(self):
        """The target-agnostic jitted ref executable (legacy shim
        attribute; prefer :meth:`jitted_variant`)."""
        if self._jitted is None:
            self._jitted = self._jit(self.fn)
        return self._jitted

    def jitted_variant(self, name: str = "ref",
                       donate: bool = True) -> Callable:
        """The target-agnostic jitted executable of one variant (unknown
        names fall back to ``ref``, like :meth:`executable`).

        ``donate=False`` compiles without buffer donation even when the
        region declares ``donate_args`` — the form staging executors and
        calibration loops (which re-call with the same arguments) use."""
        name = self.resolve(name)
        dflag = bool(donate and self.donate_args)
        key = (name, dflag)
        j = self._jvar.get(key)
        if j is None:
            if name == "ref" and dflag == bool(self.donate_args):
                j = self.jitted          # donating exactly like _jit(fn)
            elif dflag:
                j = self._jit(self.impl_fn(name))
            else:
                j = jax.jit(umem.device_operands(self.impl_fn(name)))
            self._jvar[key] = j
        return j

    @property
    def region_name(self) -> str:
        """Legacy shim attribute; prefer ``.name``."""
        return self.name

    def executable(self, target: str = "default", impl: str = "ref",
                   donate: bool = True) -> Callable:
        """The compiled executable for one (routing target, variant) pair.

        ``default`` runs wherever operands already live (the APU model);
        ``host``/``device`` pin the call to that backend — the two
        executables of the paper's ``if(target: ...)`` clause.  ``impl``
        names a registered variant (unknown names fall back to ``ref``,
        the declare-variant base-function rule).  ``donate=False``
        disables ``donate_args`` for this executable (staging executors,
        calibration loops)."""
        impl = self.resolve(impl)
        key = (target, impl, bool(donate and self.donate_args))
        if key not in self._exec:
            jfn = self.jitted_variant(impl, donate=donate)
            if target == "default":
                call = jfn
            elif target == "host":
                def call(*args, _jfn=jfn, **kwargs):
                    args, kwargs = _to_host((args, kwargs))
                    with jax.default_device(host_device()):
                        return _jfn(*args, **kwargs)
            else:
                def call(*args, _jfn=jfn, _dev=accel_device(), **kwargs):
                    with jax.default_device(_dev):
                        return _jfn(*args, **kwargs)

            self._exec[key] = call
        return self._exec[key]

    # -- direct invocation ----------------------------------------------
    def __call__(self, *args, **kwargs):
        """Calling a region directly runs its default executable and
        self-times into the ledger — the pre-executor behavior of
        ``offload_region``'s runner closure."""
        t0 = time.perf_counter()
        out = self.jitted(*args, **kwargs)
        jax.block_until_ready(out)
        self.ledger.record(self.name, device=self.offloaded,
                           offloaded=self.offloaded,
                           compute_s=time.perf_counter() - t0,
                           elems=self.size_fn(args, kwargs), impl="ref")
        return out

    # -- legacy adapter --------------------------------------------------
    @classmethod
    def from_legacy(cls, obj) -> "Region":
        """Adapt a pre-regions closure (``.jitted``/``.offloaded``/
        ``.region_name`` attributes) without re-registering it."""
        r = cls.__new__(cls)
        r.name = getattr(obj, "region_name",
                         getattr(obj, "__name__", "region"))
        r.fn = obj
        r.offloaded = bool(getattr(obj, "offloaded", True))
        r.size_fn = default_size
        r.arg_spaces = None
        r.result_space = None
        r.stencil = None
        r.halo_args = None
        r.donate_args = None
        r.ledger = GLOBAL_LEDGER
        r._jitted = getattr(obj, "jitted", None) or jax.jit(obj)
        r._variants = {"ref": obj}
        r._jvar = {("ref", False): r._jitted}
        r._exec = {}
        r.__name__ = getattr(obj, "__name__", "region")
        r.__qualname__ = r.__name__
        r._param_index = {}
        return r


#: fallback adapter cache for legacy callables that reject attribute
#: assignment (__slots__/frozen) — without it every run() would build a
#: fresh Region and register a new uniquified ledger row
_LEGACY_REGIONS = weakref.WeakKeyDictionary()


def as_region(obj) -> Region:
    """Coerce anything executable into a Region (identity for Regions)."""
    if isinstance(obj, Region):
        return obj
    cached = getattr(obj, "_as_region", None)
    if cached is not None:
        return cached
    try:
        cached = _LEGACY_REGIONS.get(obj)
    except TypeError:                      # unhashable / not weakref-able
        cached = None
    if cached is not None:
        return cached
    r = Region.from_legacy(obj)
    try:
        obj._as_region = r
    except (AttributeError, TypeError):    # frozen objects: weak-cache
        try:
            _LEGACY_REGIONS[obj] = r
        except TypeError:                  # pragma: no cover
            pass
    return r


def region(name: Optional[str] = None, *, offloaded: bool = True,
           ledger: Optional[Ledger] = None, size_fn: Optional[Callable] = None,
           placement: Optional[Mapping[Any, MemSpace]] = None,
           result_space: Any = None,
           stencil: Optional[Sequence[Tuple[int, int]]] = None,
           halo_args: Optional[Sequence[Any]] = None,
           donate_args: Optional[Sequence[int]] = None):
    """Decorator: mark a function as one offloadable region (listings 4-6).

        @region("Amul", placement={0: MemSpace.DEVICE},
                stencil=dia.STENCIL_OFFSETS, halo_args=("x",))
        def amul(diag, off, x): ...
    """
    def wrap(fn: Callable) -> Region:
        return Region(name=name or getattr(fn, "__name__", "region"),
                      fn=fn, offloaded=offloaded,
                      size_fn=size_fn or default_size,
                      arg_spaces=placement, result_space=result_space,
                      stencil=stencil, halo_args=halo_args,
                      donate_args=donate_args,
                      ledger=ledger or GLOBAL_LEDGER)
    return wrap


# ---------------------------------------------------------------------------
# Policy axes: routing, staging, placement
# ---------------------------------------------------------------------------

class Router(Protocol):
    def target(self, region: Region, args, kwargs,
               size: Optional[int] = None) -> str: ...


@dataclasses.dataclass
class StaticRouter:
    """Mode-style routing: offloaded regions go one place, the rest another.

    ``default`` means "run wherever the operands live" — the APU model where
    switching sides implies no data motion."""
    offloaded_target: str = "default"
    fallback_target: str = "default"

    def target(self, region: Region, args, kwargs,
               size: Optional[int] = None) -> str:
        return self.offloaded_target if region.offloaded \
            else self.fallback_target


@dataclasses.dataclass
class SizeRouter:
    """The ``if(target: n > TARGET_CUT_OFF)`` clause (paper C3), absorbed
    from the retired ``TargetDispatch`` shim so it runs *inside* any
    executor."""
    cutoff: int = DEFAULT_CUTOFF

    def target(self, region: Region, args, kwargs,
               size: Optional[int] = None) -> str:
        if not region.offloaded:
            return "host"
        n = region.size_fn(args, kwargs) if size is None else size
        return "device" if n > self.cutoff else "host"


class Stager(Protocol):
    stages: bool
    def stage_in(self, region: Region, args, kwargs) -> Tuple[tuple, float, int]: ...
    def stage_out(self, region: Region, out, staged_in=None) -> Tuple[Any, float, int]: ...


class NullStager:
    """APU / host model: crossing the boundary moves no bytes."""
    stages = False

    def stage_in(self, region, args, kwargs):
        return (args, kwargs), 0.0, 0

    def stage_out(self, region, out, staged_in=None):
        return out, 0.0, 0


# copy-into-donated-buffer: XLA may alias the output onto the pooled
# buffer's storage, which is what "reuse" means for immutable arrays
# (select keeps the dtype exact — src and dst match by construction).
# Module-level so every stager shares one jit cache per shape/dtype.
_copy_into = jax.jit(
    umem.device_operands(lambda src, dst: jnp.where(True, src, dst)),
    donate_argnums=(1,))

# slab-into-donated-buffer: the chunked form of _copy_into for
# budget-bounded staging — lands one leading-axis slab of the source in
# the (donated) destination, so a leaf larger than the device budget's
# staging granule streams through it in slabs instead of migrating as
# one transient allocation.
_copy_slab = jax.jit(
    umem.device_operands(
        lambda dst, src, start: jax.lax.dynamic_update_slice_in_dim(
            dst, src, start, axis=0)),
    donate_argnums=(0,))


def _chunked_copy_into(h, dst, chunk_bytes: int):
    """Stage host array ``h`` into the pooled device buffer ``dst`` in
    leading-axis slabs of at most ``chunk_bytes`` — the managed-memory
    page-migration model with the page size set by a
    :class:`~repro.core.oversub.MemoryBudget`.  Values are identical to a
    single ``_copy_into`` (same bytes, different copy granularity), which
    is what keeps budgeted replay on the §2 parity contract.  Returns
    ``(result, n_chunks)``."""
    rows = int(h.shape[0]) if h.ndim else 0
    row_bytes = h.nbytes // rows if rows else h.nbytes
    slab = max(1, int(chunk_bytes) // max(int(row_bytes), 1))
    if not rows or rows <= slab:
        return _copy_into(h, dst), 1
    y = dst
    n = 0
    for start in range(0, rows, slab):
        y = _copy_slab(y, h[start:start + slab], start)
        n += 1
    return y, n


@dataclasses.dataclass
class MigrationStager:
    """Managed-memory dGPU model: every host<->device crossing is a REAL
    out-of-place copy (paper §5, the >65% migration fraction of Fig 6).

    Inbound, operands are read out of host memory and migrated into device
    buffers recycled through the :class:`DeviceBufferPool` (donation hands
    the pooled storage to XLA — paper C4's "reuse instead of alloc/free
    churn").  Outbound, results are read back and landed in pooled host
    staging pages before being re-wrapped as host-space arrays, so the next
    host consumer sees host memory — and the next offloaded region pays the
    migration again.

    ``budget`` (a :class:`~repro.core.oversub.MemoryBudget`) bounds the
    transient staging granule: leaves larger than the budget's
    ``staging_chunk_bytes()`` migrate in leading-axis slabs through
    ``_chunked_copy_into`` instead of one copy, so grids beyond device
    capacity stream through the budget rather than blowing past it.
    Chunking changes copy granularity, never values."""
    arena: UnifiedArena = dataclasses.field(default_factory=UnifiedArena)
    host_pool: HostStagingPool = dataclasses.field(
        default_factory=HostStagingPool)
    device_pool: DeviceBufferPool = dataclasses.field(
        default_factory=DeviceBufferPool)
    budget: Optional[Any] = None
    stages = True

    def _migrate_in(self, x, rotation=None):
        if not hasattr(x, "nbytes"):
            return x
        h = np.asarray(x)                               # host page read
        pool = rotation.pool if rotation is not None else self.device_pool
        dst = pool.acquire(h.shape, h.dtype)
        chunk = self.budget.staging_chunk_bytes() \
            if self.budget is not None else None
        if chunk is not None and h.nbytes > chunk:
            y, n = _chunked_copy_into(h, dst, chunk)    # budgeted slabs
            self.budget.note_chunks(n)
        else:
            y = _copy_into(h, dst)                      # host -> device copy
        if rotation is not None:
            # the copy DONATES dst; the bank must hold the result (which
            # owns the recycled storage), never the consumed buffer
            rotation.register(y)
        return y

    @staticmethod
    def _aliases(y, buf) -> bool:
        """Does the jax Array share storage with the numpy staging buffer?
        On CPU backends device_put from numpy may be zero-copy."""
        try:
            return y.unsafe_buffer_pointer() == \
                buf.__array_interface__["data"][0]
        except Exception:
            return True                                 # conservative

    def _migrate_out(self, x, pending: Optional[list] = None):
        """Land one result in a pooled host page and re-wrap it host-side.

        The wrap may COPY the page *asynchronously*: the page cannot go
        back to the pool (where the very next result lands a copyto)
        until that read has finished, or a delayed copy reads recycled
        bytes — the PR-2 replay-corruption race.  Ownership is therefore
        decided only after the wrap is ready: standalone calls block here;
        ``stage_out`` passes ``pending`` to collect (wrap, page) pairs,
        block ONCE on the whole staged tree (copies overlap), and settle
        afterwards."""
        if not isinstance(x, jax.Array):
            return x
        h = np.asarray(jax.device_get(x))               # device -> host copy
        buf = self.host_pool.acquire(h.shape, h.dtype)
        np.copyto(buf, h)                               # pooled host pages
        y = umem.place(buf, self.arena.host_space, self.arena.device)
        if not isinstance(y, jax.Array):                # no host space: wrap
            y = jax.device_put(buf, self.arena.device)
        if pending is None:
            jax.block_until_ready(y)
            self._settle_pages([(y, buf)])
        else:
            pending.append((y, buf))
        return y

    def _settle_pages(self, pending) -> None:
        """Decide page ownership for READY wraps: recycle the page when the
        wrap copied; a zero-copy device_put leaves the wrap aliasing the
        pooled bytes (CPU backends), so there the page returns to the pool
        only when the result array dies — the Umpire model: the app
        "frees" host memory by dropping the result."""
        for y, buf in pending:
            if self._aliases(y, buf):
                try:
                    weakref.finalize(y, self.host_pool.release, buf)
                except TypeError:          # pragma: no cover - no weakrefs
                    pass
            else:
                self.host_pool.release(buf)

    def stage_in(self, region, args, kwargs):
        t0 = time.perf_counter()
        nbytes = self.arena.bytes_of((args, kwargs))
        staged = jax.tree.map(self._migrate_in, (args, kwargs))
        jax.block_until_ready(staged)
        return staged, time.perf_counter() - t0, nbytes

    def stage_leaves(self, leaves, rotation=None):
        """Migrate a flat list of leaves host->device, acquiring through a
        :class:`~repro.core.pool.BufferRotation` bank when one is given —
        the double-buffered path of the async lookahead replay
        (``repro.core.program``).  Returns (staged_leaves, seconds, bytes)."""
        t0 = time.perf_counter()
        nbytes = self.arena.bytes_of(leaves)
        staged = [self._migrate_in(x, rotation) for x in leaves]
        jax.block_until_ready(staged)
        return staged, time.perf_counter() - t0, nbytes

    def stage_out(self, region, out, staged_in=None):
        t0 = time.perf_counter()
        nbytes = self.arena.bytes_of(out)
        pending: list = []
        staged = jax.tree.map(lambda x: self._migrate_out(x, pending), out)
        jax.block_until_ready(staged)       # all wrap copies, overlapped
        self._settle_pages(pending)
        if staged_in is not None:                       # recycle dead inputs
            for x in jax.tree.leaves(staged_in):
                if isinstance(x, jax.Array):
                    self.device_pool.release(x)
        return staged, time.perf_counter() - t0, nbytes


@dataclasses.dataclass
class Placer:
    """Placement axis: apply a region's MemSpace hints through umem.

    ``min_bytes`` is the paper-C4-style threshold: leaves smaller than it
    stay where they are (placing a scalar across spaces costs more than it
    saves).

    ``_place_tree`` is the single placement primitive every hint flows
    through — subclasses override it to make placement *conditional*
    (:class:`~repro.core.oversub.BudgetedPlacer` demotes device hints to
    host space when a memory budget lacks headroom)."""
    min_bytes: int = 0
    honor_hints: bool = True

    def _place_tree(self, tree, space: MemSpace):
        return umem.tree_place(tree, space, min_bytes=self.min_bytes)

    def place_args(self, region: Region, args, kwargs):
        if not (self.honor_hints and region.arg_spaces):
            return args, kwargs
        args = list(args)
        kwargs = dict(kwargs)
        for key, space in region.arg_spaces.items():
            if isinstance(key, str):
                if key in kwargs:
                    kwargs[key] = self._place_tree(kwargs[key], space)
                    continue
                # name hint for a positionally-passed argument
                key = region._param_index.get(key, -1)
            if isinstance(key, int) and 0 <= key < len(args):
                args[key] = self._place_tree(args[key], space)
        return tuple(args), kwargs

    def place_result(self, region: Region, out):
        if not (self.honor_hints and region.result_space is not None):
            return out
        rs = region.result_space
        if isinstance(rs, Mapping):
            # keyed form: place only the named top-level result elements
            if isinstance(out, tuple):
                placed = list(out)
                for key, space in rs.items():
                    if isinstance(key, int) and 0 <= key < len(placed):
                        placed[key] = self._place_tree(placed[key], space)
                return tuple(placed)
            if isinstance(out, dict):
                return {k: self._place_tree(v, rs[k])
                        if k in rs else v for k, v in out.items()}
            return out
        return self._place_tree(out, rs)


# ---------------------------------------------------------------------------
# Selection axis: which implementation variant runs (declare variant)
# ---------------------------------------------------------------------------

class Selector(Protocol):
    """The fourth policy axis: resolve one registered variant per call.

    ``target`` is the routing decision already made by the policy's Router
    (``default`` / ``host`` / ``device``), so selection can condition on
    where the call will run — OpenMP's ``match(device={...})`` clause."""

    def select(self, region: Region, target: str, args, kwargs,
               size: Optional[int] = None) -> str: ...


@dataclasses.dataclass
class StaticSelector:
    """One named implementation everywhere.  Regions that never registered
    the name run their base function instead — the declare-variant
    fallback, which is what lets a whole captured program replay under
    ``StaticSelector("pallas")`` when only its hot regions carry kernels."""
    impl: str = "ref"

    def select(self, region: Region, target: str, args, kwargs,
               size: Optional[int] = None) -> str:
        return region.resolve(self.impl)


#: the do-nothing selector: every region runs its decorated function, the
#: exact pre-variants behavior
DEFAULT_SELECTOR = StaticSelector("ref")


@dataclasses.dataclass
class TargetSelector:
    """Target-conditioned defaults — ``declare variant match(construct,
    device)``: device-side calls (including ``default``, the APU's
    resident execution) prefer the custom kernel, host-side calls the
    host-tuned path, with the usual fallback to ``ref``."""
    device_impl: str = "pallas"
    host_impl: str = "host"

    def select(self, region: Region, target: str, args, kwargs,
               size: Optional[int] = None) -> str:
        want = self.host_impl if target == "host" else self.device_impl
        return region.resolve(want)


def size_bucket(n: int) -> int:
    """Power-of-two size bucket: bucket ``b`` covers ``[2^(b-1), 2^b)``.
    The autotune analogue of the paper's single TARGET_CUT_OFF — coarse
    enough that a handful of calibration sizes covers a workload, fine
    enough that the host/kernel crossover lands in its own cell."""
    return int(n).bit_length()


@dataclasses.dataclass
class AutotuneSelector:
    """Calibrated variant selection: winners per (region, target,
    size-bucket), measured by :meth:`calibrate` the way
    ``AdaptivePolicy.calibrate`` measures the routing cutoff, and persisted
    on the region's ledger row (``coverage_report()["calibrated_variants"]``).

    Uncalibrated cells fall back to the nearest calibrated bucket of the
    same (region, target), then to ``fallback`` (default: ``ref``)."""
    fallback: Any = dataclasses.field(
        default_factory=lambda: StaticSelector("ref"))
    winners: Dict[Tuple[str, str, int], str] = dataclasses.field(
        default_factory=dict)

    def select(self, region: Region, target: str, args, kwargs,
               size: Optional[int] = None) -> str:
        n = region.size_fn(args, kwargs) if size is None else size
        b = size_bucket(n)
        win = self.winners.get((region.name, target, b))
        if win is None:
            near = [(abs(bb - b), bb) for (rn, t, bb) in self.winners
                    if rn == region.name and t == target]
            if near:
                win = self.winners[(region.name, target, min(near)[1])]
        if win is None:
            return self.fallback.select(region, target, args, kwargs, size=n)
        return region.resolve(win)

    def calibrate(self, target_region, make_args: Callable[[int], tuple],
                  sizes: Sequence[int] = (256, 4096, 65536),
                  targets: Sequence[str] = ("default",),
                  reps: int = 10, ledger: Optional[Ledger] = None) -> dict:
        """Time every registered variant of ``target_region`` over a size
        ladder per routing target; store the winner per (target, bucket)
        and persist it with the region's ledger row.

        ``make_args(n)`` builds one positional argument tuple of problem
        size ~``n``; the bucket is derived from the region's own
        ``size_fn`` on those arguments, so calibration and selection agree
        on the size measure.  Returns ``{(target, bucket): winner}``."""
        r = as_region(target_region)
        chosen = {}
        for tgt in targets:
            for n in sorted(sizes):
                args = make_args(n)
                best, best_t = "ref", float("inf")
                for name in r.variants:
                    # donate=False: the timing loop re-calls with the same
                    # argument buffers
                    ex = r.executable(tgt, name, donate=False)
                    out = ex(*args)
                    jax.block_until_ready(out)          # compile + warm
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        out = ex(*args)
                    jax.block_until_ready(out)
                    dt = (time.perf_counter() - t0) / reps
                    if dt < best_t:
                        best, best_t = name, dt
                b = size_bucket(r.size_fn(args, {}))
                self.winners[(r.name, tgt, b)] = best
                chosen[(tgt, b)] = best
                r.ledger.set_calibrated_variant(r.name, tgt, b, best)
                if ledger is not None and ledger is not r.ledger:
                    ledger.set_calibrated_variant(r.name, tgt, b, best)
        return chosen


# ---------------------------------------------------------------------------
# ExecutionPolicy = placement x routing x staging x selection
# ---------------------------------------------------------------------------

@runtime_checkable
class ExecutionPolicy(Protocol):
    """What an Executor needs: a name and the composable axes.  ``selector``
    is optional for backward compatibility — executors treat a missing
    attribute as ``DEFAULT_SELECTOR`` (always ``ref``)."""
    name: str
    router: Router
    stager: Stager
    placer: Placer


def policy_selector(policy) -> Selector:
    """The policy's selection axis, defaulting to ref-everywhere for
    pre-variants policy objects."""
    return getattr(policy, "selector", None) or DEFAULT_SELECTOR


@dataclasses.dataclass
class ComposedPolicy:
    """A concrete ExecutionPolicy assembled from the four axes."""
    name: str
    router: Any = dataclasses.field(default_factory=StaticRouter)
    stager: Any = dataclasses.field(default_factory=NullStager)
    placer: Any = dataclasses.field(default_factory=Placer)
    selector: Any = dataclasses.field(
        default_factory=lambda: StaticSelector("ref"))


class UnifiedPolicy(ComposedPolicy):
    """APU model (paper §3): operands stay where they are, regions run
    back-to-back, zero staging by construction."""

    def __init__(self, placer: Optional[Placer] = None,
                 selector: Optional[Selector] = None):
        super().__init__("unified", StaticRouter("default", "default"),
                         NullStager(), placer or Placer(),
                         selector or StaticSelector("ref"))


class HostPolicy(ComposedPolicy):
    """dCPU model: every region — directive or not — runs on the host."""

    def __init__(self, placer: Optional[Placer] = None,
                 selector: Optional[Selector] = None):
        super().__init__("host", StaticRouter("host", "host"),
                         NullStager(), placer or Placer(),
                         selector or StaticSelector("ref"))


class DiscretePolicy(ComposedPolicy):
    """Managed-memory dGPU model: offloaded regions run on the device and
    pay real staging copies both ways (paper Fig 6).

    ``budget`` (a :class:`~repro.core.oversub.MemoryBudget`) makes the
    policy oversubscription-aware: the device pool charges its resident
    bytes against it and the stager migrates in budget-sized slabs, so
    grids beyond the logical device capacity stream through instead of
    blowing past it."""

    def __init__(self, arena: Optional[UnifiedArena] = None,
                 host_pool: Optional[HostStagingPool] = None,
                 device_pool: Optional[DeviceBufferPool] = None,
                 placer: Optional[Placer] = None,
                 selector: Optional[Selector] = None,
                 budget: Optional[Any] = None):
        arena = arena or UnifiedArena()
        if device_pool is None:
            device_pool = DeviceBufferPool(budget=budget)
        super().__init__("discrete", StaticRouter("device", "default"),
                         MigrationStager(arena,
                                         host_pool or HostStagingPool(),
                                         device_pool,
                                         budget=budget),
                         placer or Placer(),
                         selector or StaticSelector("ref"))
        self.arena = arena
        self.budget = budget


class AdaptivePolicy(ComposedPolicy):
    """Calibrated size-based routing *inside* an executor — the
    ``TARGET_CUT_OFF`` clause as a policy axis, which the pre-regions split
    (TargetDispatch vs executors) made structurally impossible."""

    def __init__(self, cutoff: int = DEFAULT_CUTOFF,
                 stager: Optional[Stager] = None,
                 placer: Optional[Placer] = None,
                 selector: Optional[Selector] = None,
                 budget: Optional[Any] = None):
        if stager is None and budget is not None:
            # oversubscription-aware adaptive: device-routed calls pay
            # budget-chunked staging like the discrete model
            stager = MigrationStager(
                device_pool=DeviceBufferPool(budget=budget), budget=budget)
        super().__init__("adaptive", SizeRouter(cutoff),
                         stager or NullStager(), placer or Placer(),
                         selector or StaticSelector("ref"))
        self.budget = budget

    @property
    def cutoff(self) -> int:
        return self.router.cutoff

    @cutoff.setter
    def cutoff(self, value: int) -> None:
        self.router.cutoff = value

    def calibrate(self, target_region, make_args: Callable[[int], tuple],
                  sizes: Sequence[int] = (256, 1024, 4096, 16384, 65536),
                  reps: int = 20, ledger: Optional[Ledger] = None) -> int:
        """Reproduce the paper's empirical TARGET_CUT_OFF choice: time both
        executables over a size ladder, set cutoff to the crossover, and
        record the choice with the region's ledger row.

        ``ledger`` additionally mirrors the cutoff into another ledger's
        row of the same bare name (get-or-create) — note that a foreign
        ledger holding a *different* region under that name would receive
        the mirror on that row."""
        r = as_region(target_region)
        crossover = None
        for n in sorted(sizes):
            args = make_args(n)
            ts = {}
            for tgt in ("host", "device"):
                ex = r.executable(tgt, donate=False)
                out = ex(*args)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = ex(*args)
                jax.block_until_ready(out)
                ts[tgt] = (time.perf_counter() - t0) / reps
            if ts["device"] < ts["host"]:
                crossover = n
                break
        if crossover is None:
            crossover = max(sizes) + 1
        self.cutoff = crossover
        # the region's OWN ledger is authoritative for r.name; an explicit
        # foreign ledger gets a bare-name mirror (see docstring caveat)
        r.ledger.set_cutoff(r.name, crossover)
        if ledger is not None and ledger is not r.ledger:
            ledger.set_cutoff(r.name, crossover)
        return crossover


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

class Executor:
    """Replays region programs under one ExecutionPolicy, accounting every
    call into one Ledger.

    Return contract: ``run`` ALWAYS returns jax Arrays (or the region's
    non-array outputs unchanged), regardless of policy.  The discrete policy
    stages results into host-space arrays — it does not leak numpy, which
    the old DiscreteExecutor did, silently changing downstream types per
    mode."""

    def __init__(self, policy: ExecutionPolicy, ledger: Optional[Ledger] = None):
        self.policy = policy
        self.ledger = ledger or Ledger(policy.name)
        self.mode = policy.name
        # staging policies carry pools — attach them so coverage_report()
        # surfaces byte-level pool accounting next to the staging fractions
        stager = getattr(policy, "stager", None)
        for pool_name, attr in (("host_staging", "host_pool"),
                                ("device_buffer", "device_pool")):
            pool = getattr(stager, attr, None)
            if pool is not None:
                self.ledger.attach_pool(pool_name, pool)
        # region -> (ledger -> row name), weak at both levels: entries die
        # with their region/ledger instead of pinning compiled executables
        # for the executor's lifetime, and object identity (not id()) rules
        # out stale hits after a ledger swap recycles an address
        self._row_names = weakref.WeakKeyDictionary()

    def _row_name(self, r: Region) -> str:
        """Ledger row for this region in THIS executor's ledger.  Distinct
        region objects that happen to share a name (registered in different
        ledgers) must not merge into one row — re-uniquify on first record."""
        per_region = self._row_names.get(r)
        if per_region is None:
            per_region = weakref.WeakKeyDictionary()
            self._row_names[r] = per_region
        name = per_region.get(self.ledger)
        if name is None:
            name = r.name if r.ledger is self.ledger \
                else self.ledger.register(r.name, r.offloaded)
            per_region[self.ledger] = name
        return name

    def run(self, target_region, *args, **kwargs):
        r = as_region(target_region)
        pol = self.policy
        n = r.size_fn(args, kwargs)
        tgt = pol.router.target(r, args, kwargs, size=n)
        # resolve() here, not just in executable(): custom selectors may
        # return unregistered names, and the ledger must record what RAN
        impl = r.resolve(policy_selector(pol).select(r, tgt, args, kwargs,
                                                     size=n))
        args, kwargs = pol.placer.place_args(r, args, kwargs)
        staging_s = 0.0
        staging_b = 0
        stage = pol.stager.stages and r.offloaded and tgt != "host"
        staged_in = None
        if stage:
            (args, kwargs), s, b = pol.stager.stage_in(r, args, kwargs)
            staged_in = (args, kwargs)
            staging_s += s
            staging_b += b
        t0 = time.perf_counter()
        # donation is disabled under staging policies: staged operands may
        # alias pooled pages whose lifetime the stager manages; and a
        # host-placed operand cannot back a result in device memory
        donate = not pol.stager.stages and not _host_placed(
            [args[i] for i in (r.donate_args or ()) if i < len(args)])
        out = r.executable(tgt, impl, donate=donate)(*args, **kwargs)
        jax.block_until_ready(out)
        compute_s = time.perf_counter() - t0
        if stage:
            out, s, b = pol.stager.stage_out(r, out, staged_in)
            staging_s += s
            staging_b += b
        out = pol.placer.place_result(r, out)
        device = r.offloaded if tgt == "default" else (tgt == "device")
        self.ledger.record(self._row_name(r), device=device,
                           offloaded=r.offloaded,
                           compute_s=compute_s, staging_s=staging_s,
                           staging_bytes=staging_b, elems=n, impl=impl)
        return out

    def report(self) -> dict:
        rep = self.ledger.coverage_report()
        rep["mode"] = self.mode
        return rep


POLICIES = {
    "unified": UnifiedPolicy,
    "discrete": DiscretePolicy,
    "host": HostPolicy,
    "adaptive": AdaptivePolicy,
}


def make_policy(mode: str, **kw) -> ComposedPolicy:
    return POLICIES[mode](**kw)
