"""Multi-APU region programs: shard a captured replay across a device mesh.

The paper ports OpenFOAM to ONE MI300A; a production node ships four of
them linked by Infinity Fabric, and the follow-up literature ("Inter-APU
Communication on AMD MI300A Systems via Infinity Fabric", the Grace-Hopper
unified-memory studies) shows that scaling a unified-memory code across a
node hinges on two things the single-device story never surfaces:
topology-aware placement and *communication accounting* — knowing how much
of a step is compute, how much is staging, and how much is inter-APU
boundary traffic.

This module adds that node dimension to captured programs
(:mod:`repro.core.program`):

* :func:`shard_program` / :class:`ShardedProgram` — wrap a captured
  :class:`~repro.core.program.RegionProgram` for a 1-D/2-D/3-D ``jax.Mesh``
  of N simulated APUs (CPU containers simulate the node with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the
  ``launch.mesh`` trick; :func:`repro.launch.mesh.make_apu_mesh` builds the
  mesh, ``make_apu_mesh((2, 2))`` for a 2-D decomposition that cuts
  surface-to-volume).

* :class:`ShardExecutor` — the executor that replays the trace
  domain-decomposed: every array operand is placed with a ``NamedSharding``
  splitting one array dimension per mesh axis (``shard_dim``), every region
  executes SPMD across all APUs (XLA partitions the *identical* region
  function — application code is untouched, the paper's C1 claim at node
  scale), and regions that declare a ``stencil`` get an explicit
  **halo-exchange region** scheduled around them.

* halo exchange — the width is inferred from the region's declared DIA
  offset table (:data:`repro.cfd.dia.STENCIL_OFFSETS`, see
  :func:`halo_width` / :meth:`Region.stencil_width`).  The exchange itself
  is a bit-exact value identity, ``roll(roll(x, +w), -w)`` along each
  decomposed dimension: XLA partitions each roll into exactly the
  boundary-plane transfers a width-``w`` halo swap performs (w planes
  across every shard boundary, each direction), so the measured wall time
  *is* the inter-APU traffic cost while the value — and therefore the
  replayed numerics — is unchanged.  It appears in every per-device ledger
  as a ``halo(<region>)`` row carrying ``exchange_s`` / ``exchange_bytes``.

* **exchange schedules** (the halo-exchange-tax mitigation, ROADMAP 2):

  - ``overlap=False`` — *sequential*: exchange, then compute consuming the
    exchanged operands (the PR-3 baseline; every exchange is exposed wall
    time).
  - ``overlap=True`` (default) — *overlapped*: the exchange is dispatched
    asynchronously right after the region's interior compute (same
    thread — collectives deadlock if two threads interleave their
    per-device enqueue order, see :meth:`_dispatch_exchange`) and a
    single background worker waits out the transfer while the main loop
    moves on; because the exchange is a value identity, the interior IS
    the whole region and never waits on it.  A bounded lookahead (the
    :class:`~repro.core.program.AsyncExecutor` machinery) additionally
    dispatches the next due exchange whose operands are already
    resolvable before blocking on the *current* op's compute, so step
    N+1's halo hides behind step N.  Hidden seconds land as ``overlap_s``
    on the halo row and are excluded from ledger totals (``total =
    compute + staging + exchange - overlap``).
  - ``split_stencil=True`` — *causal split*: the stencil region runs as
    real ``interior``/``boundary`` sub-regions.  The interior pass computes
    the full field from un-exchanged operands while the exchange runs
    behind it; the ``boundary(<region>)`` pass then recomputes from the
    exchanged operands and blends only the ghost-adjacent band (a
    ``where`` on the shard-local index).  This is the structural form of
    the overlap — boundary values causally consume the exchange — at the
    cost of a second (boundary-masked) pass.

* **wide halos** — ``halo_multiplier=k`` provisions ghost zones ``k`` times
  the stencil width and performs the exchange every ``k``-th application
  of each stencil region: ``1/k`` as many syncs, each moving ``k``-wide
  boundary slabs (same total bytes, amortized latency — the multi-step
  ghost-zone trade of docs/SCALING.md).  The schedule is deterministic
  (per-region application counters), so replays stay reproducible.

* per-device ledgers — each simulated APU owns a
  :class:`~repro.core.ledger.Ledger`.  The decomposition is symmetric, so
  each device's rows record its **local share**: ``1/N`` of every measured
  wall interval and of every byte/element count.  Summing the per-device
  ledgers (``Ledger.merged``) therefore reproduces the measured node wall
  split exactly; ``ShardExecutor.report()`` returns that aggregate with a
  ``per_device`` breakdown splitting compute, staging, exchange, and
  overlap time.

Any :class:`~repro.core.regions.ExecutionPolicy` applies:

- ``UnifiedPolicy`` — operands stay resident in the decomposition; only
  halo-exchange regions move bytes between APUs (the paper's APU model,
  scaled out: migration deleted, Fabric traffic remains).
- ``DiscretePolicy`` — every region call stages its operands host->APUs
  (scatter through pooled sharded buffers) and its results APUs->host: the
  managed-memory node where the host bounce multiplies with N.
- ``AdaptivePolicy`` — calls under the calibrated cutoff gather to the
  host and run there (small problems don't amortize a node), the rest run
  decomposed.

Numerics: region math is elementwise/stencil arithmetic partitioned by
XLA, so sharded replay is bit-comparable to the single-device replay of
the same program under every schedule; only compiler re-fusion across
different sharding signatures (and the split schedule's second compilation
context) can perturb results, within the float32 tolerance documented in
docs/DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ledger import (DISPATCH, EXCHANGE, EXCHANGE_WAIT, PLACE,
                               REGION, SYNC, Ledger, span)
from repro.core.pool import DeviceBufferPool
from repro.core.program import (Lit, Ref, RegionProgram, _is_array,
                                _resolver, interval_overlap)
from repro.core.regions import (ExecutionPolicy, Executor, Region,
                                UnifiedPolicy, _chunked_copy_into,
                                _copy_into, policy_selector)
from repro.core.umem import replicated_sharding, shard_along_nd


def halo_width(offsets, axis: int) -> int:
    """Halo width a decomposition along grid axis ``axis`` must exchange
    for a stencil with DIA offset table ``offsets`` — the maximum reach of
    any band along that axis (see :meth:`Region.stencil_width`).

        halo_width(dia.STENCIL_OFFSETS, axis=2)                  -> 1
        halo_width(dia.compose_offsets(S, S), axis=2)            -> 2
        halo_width(None, axis=2)                                 -> 0
    """
    if not offsets:
        return 0
    return max((abs(d) for ax, d in offsets if ax == axis), default=0)


@dataclasses.dataclass
class _Exchange:
    """Result of one (possibly background) halo-exchange execution."""
    outs: Dict[int, Any]        # operand leaf index -> exchanged leaf
    nbytes: int                 # per-device bytes sent over the Fabric
    t0: float
    t1: float
    op: int                     # index of the op it serves in the replay

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class ShardExecutor:
    """Replays :class:`RegionProgram`\\ s domain-decomposed over a mesh of
    simulated APUs, under any :class:`ExecutionPolicy`, with one
    :class:`Ledger` per device.

    ``shard_dim`` selects the array dimension(s) split over the mesh
    ax(es).  For a 1-D mesh the default is ``-1`` (the trailing dimension,
    which for ``[nx,ny,nz]`` CFD fields and ``[6,nx,ny,nz]`` DIA
    coefficient stacks alike is the grid z axis); an N-axis mesh defaults
    to the N trailing dimensions (2-D: y and z).  Leaves whose extent does
    not divide by a mesh axis replicate along it.  ``stencil_axis`` is the
    *grid* axis each sharded dimension decomposes (default
    ``shard_dim % 3``); halo widths are inferred against it from each
    region's declared ``stencil`` offsets.

    ``halo_multiplier``, ``overlap``, and ``split_stencil`` select the
    exchange schedule (module docstring); ``lookahead_depth`` bounds how
    far ahead the overlap thread may look for the next due exchange.

    ``prog.replay(shard_executor, *inputs)`` dispatches here through the
    standard ``replay_program`` hook, so a ShardExecutor drops in anywhere
    an :class:`Executor` or ``AsyncExecutor`` does.
    """

    def __init__(self, policy: Optional[ExecutionPolicy], mesh,
                 axis=None, shard_dim=None, stencil_axis=None, *,
                 halo_multiplier: int = 1, overlap: bool = True,
                 split_stencil: bool = False, lookahead_depth: int = 2):
        self.policy = policy or UnifiedPolicy()
        self.mesh = mesh
        if axis is None:
            axes = tuple(mesh.axis_names)
        elif isinstance(axis, str):
            axes = (axis,)
        else:
            axes = tuple(axis)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no axis {a!r}: {mesh.axis_names}")
        self.axes: Tuple[str, ...] = axes
        if shard_dim is None:
            dims: Tuple[int, ...] = tuple(range(-len(axes), 0))
        elif isinstance(shard_dim, int):
            dims = (shard_dim,)
        else:
            dims = tuple(shard_dim)
        if len(dims) != len(axes):
            raise ValueError(f"{len(axes)} mesh axes but {len(dims)} "
                             f"shard dims: {axes} vs {dims}")
        self.shard_dims: Tuple[int, ...] = dims
        if stencil_axis is None:
            st: Tuple[int, ...] = tuple(d % 3 for d in dims)
        elif isinstance(stencil_axis, int):
            st = (stencil_axis,) * len(dims)
        else:
            st = tuple(stencil_axis)
        self.stencil_axes: Tuple[int, ...] = st
        self.axis_sizes: Tuple[int, ...] = tuple(
            int(mesh.shape[a]) for a in axes)
        self.n_devices = int(mesh.devices.size)
        self.halo_multiplier = max(1, int(halo_multiplier))
        self.overlap = bool(overlap)
        self.split_stencil = bool(split_stencil)
        self.lookahead_depth = max(1, int(lookahead_depth))
        # 1-D scalar views of the decomposition (PR-3 API surface)
        self.axis = axes[0]
        self.shard_dim = dims[0]
        self.stencil_axis = st[0]
        shape_str = "x".join(str(s) for s in self.axis_sizes)
        tag = self.axes[0] if len(axes) == 1 else "mesh"
        self.mode = f"{self.policy.name}+sharded[{shape_str}x{tag}]"
        #: one ledger per simulated APU; each records its 1/N local share
        self.ledgers: List[Ledger] = [
            Ledger(f"{self.policy.name}@{tag}{i}")
            for i in range(self.n_devices)]
        # host-routed calls (adaptive cutoff) run once, undecomposed — they
        # belong to the node, not to any one APU
        self.host_ledger = Ledger(f"{self.policy.name}@host")
        self._inner = Executor(self.policy, self.host_ledger)
        self._replicated = replicated_sharding(mesh)
        self._sharding_cache: dict = {}      # (ndim, extents) -> sharding
        # captured constants scatter across the mesh ONCE per executor, not
        # once per replayed step; keying by the Lit descriptor object keeps
        # it alive, so a recycled address can never alias a stale entry
        self._lit_cache: dict = {}           # Lit descriptor -> placed leaf
        # same-named distinct regions must not merge into one row (the
        # Executor._row_name contract, upheld per executor here — every
        # per-device ledger shares this executor's row names)
        self._row_names = weakref.WeakKeyDictionary()      # Region -> str
        self._taken_rows: set = set()
        self._halo_widths = weakref.WeakKeyDictionary()    # Region -> dict
        self._halo_cache: dict = {}     # (row, ((dim, w), ...)) -> Region
        # programs whose halo declarations already passed the static
        # verifier on this executor (repro.analysis; error findings veto
        # decomposition — a silently skipped exchange corrupts values)
        self._halo_verified = weakref.WeakKeyDictionary()  # prog -> True
        self._boundary_regions = weakref.WeakKeyDictionary()
        self._registry = Ledger(self.mode + "-rows")       # halo-name registry
        # wide-halo schedule state: applications seen per stencil row — the
        # exchange runs on every halo_multiplier-th application.  Counters
        # persist across replays so back-to-back replayed steps amortize.
        self._app_counts: Dict[str, int] = {}
        stager = self.policy.stager
        self._device_pool = getattr(stager, "device_pool", None) \
            or DeviceBufferPool()
        # oversubscription: a budget-carrying stager bounds the scatter's
        # transient staging granule (see regions._chunked_copy_into)
        self._staging_budget = getattr(stager, "budget", None)

    @property
    def schedule(self) -> str:
        if self.split_stencil:
            return "split"
        return "overlap" if self.overlap else "sequential"

    # -- accounting rows -------------------------------------------------
    def _row_name(self, r: Region) -> str:
        """Ledger row for this region across ALL of this executor's
        per-device ledgers.  Distinct region objects that happen to share
        a name (registered in different app ledgers) get re-uniquified —
        the same contract ``Executor._row_name`` keeps."""
        name = self._row_names.get(r)
        if name is None:
            name = r.name
            k = 2
            while name in self._taken_rows:
                name = f"{r.name}#{k}"
                k += 1
            self._taken_rows.add(name)
            self._row_names[r] = name
        return name

    # -- placement -------------------------------------------------------
    def _assignments(self, shape) -> Tuple[Tuple[int, str, int], ...]:
        """Which array dimensions of ``shape`` this decomposition splits:
        ``(normalized_dim, mesh_axis, axis_size)`` per mesh axis whose
        assigned ``shard_dim`` exists and divides.  A dimension claimed by
        an earlier mesh axis is not re-split."""
        ndim = len(shape)
        out, used = [], set()
        for ax, dim, size in zip(self.axes, self.shard_dims,
                                 self.axis_sizes):
            if not (ndim and -ndim <= dim < ndim):
                continue
            d = dim % ndim
            if d in used:
                continue
            ext = shape[d]
            if ext >= size and ext % size == 0:
                out.append((d, ax, size))
                used.add(d)
        return tuple(out)

    def sharding_for(self, leaf):
        """The NamedSharding this decomposition gives one array leaf:
        each ``shard_dim`` split over its mesh axis when divisible,
        replicated otherwise.  Cached per (ndim, candidate extents) — the
        replay hot loop asks for every leaf of every op inside timed
        intervals."""
        shape = tuple(getattr(leaf, "shape", ()))
        ndim = len(shape)
        if not ndim:
            return self._replicated
        key = (ndim, tuple(shape[d % ndim] if -ndim <= d < ndim else -1
                           for d in self.shard_dims))
        sh = self._sharding_cache.get(key)
        if sh is None:
            asg = self._assignments(shape)
            sh = shard_along_nd(
                self.mesh, {d: ax for d, ax, _ in asg}, ndim) \
                if asg else self._replicated
            self._sharding_cache[key] = sh
        return sh

    def _place(self, x):
        sh = self.sharding_for(x)
        if isinstance(x, jax.Array) and x.sharding == sh:
            return x
        return jax.device_put(x, sh)

    def _is_sharded(self, x) -> bool:
        sh = self.sharding_for(x)
        return sh is not self._replicated and isinstance(x, jax.Array) \
            and x.sharding == sh

    # -- staging (discrete node model) -----------------------------------
    def _stage_scatter(self, leaves) -> Tuple[list, float, int, list]:
        """Migrate operand leaves host -> N APUs: read each array out of
        host memory and scatter it into a pooled sharded device buffer
        (donation recycles the pool storage, paper C4 at node scale).
        Returns (placed, seconds, bytes, acquired_buffers)."""
        t0 = time.perf_counter()
        placed, nbytes, acquired = [], 0, []
        for x in leaves:
            if not _is_array(x):
                placed.append(x)
                continue
            h = np.asarray(x)                       # host page read / gather
            sh = self.sharding_for(h)
            dst = self._device_pool.acquire(h.shape, h.dtype, sharding=sh)
            chunk = self._staging_budget.staging_chunk_bytes() \
                if self._staging_budget is not None else None
            if chunk is not None and h.nbytes > chunk:
                y, n = _chunked_copy_into(h, dst, chunk)  # budgeted slabs
                self._staging_budget.note_chunks(n)
            else:
                y = _copy_into(h, dst)              # host -> APUs scatter
            if y.sharding != sh:                    # pragma: no cover
                y = jax.device_put(y, sh)
            placed.append(y)
            acquired.append(y)
            nbytes += h.nbytes
        jax.block_until_ready(acquired)
        return placed, time.perf_counter() - t0, nbytes, acquired

    # -- halo exchange ---------------------------------------------------
    def _stencil_widths(self, r: Region) -> Optional[Dict[str, int]]:
        """Base halo width per mesh axis for region ``r`` (cached), from
        its declared stencil against each axis's grid axis; None for
        pointwise regions."""
        w = self._halo_widths.get(r)
        if w is None:
            w = {ax: halo_width(r.stencil, st)
                 for ax, st in zip(self.axes, self.stencil_axes)}
            if not any(w.values()):
                w = False
            self._halo_widths[r] = w
        return w or None

    def _halo_region(self, r: Region, items: Tuple[Tuple[int, int], ...]
                     ) -> Region:
        """The explicit halo-exchange Region for stencil region ``r`` over
        decomposed (dim, exchange_width) pairs ``items`` (cached per
        signature).  Its fn is the bit-exact roll round-trip identity
        whose partitioned form moves exactly the width-``w`` boundary
        slabs across every shard boundary, both directions."""
        row = self._row_name(r)
        key = (row, items)
        halo = self._halo_cache.get(key)
        if halo is None:
            def exchange(x, _items=items):
                for d, w in _items:
                    x = jnp.roll(jnp.roll(x, w, d), -w, d)
                return x

            halo = Region(name=f"halo({row})", fn=exchange,
                          offloaded=True, ledger=self._registry)
            halo.halo_widths = items
            self._halo_cache[key] = halo
        return halo

    def _halo_leaf_indices(self, op) -> List[int]:
        """Which operand leaves the halo exchange covers: the region's
        declared ``halo_args`` (top-level positions/names), else every
        array leaf."""
        r = op.region
        spec = getattr(r, "halo_args", None)
        if spec is None:
            return list(range(len(op.leaves)))
        keys = set(spec)
        for name in [k for k in keys if isinstance(k, str)]:
            idx = r._param_index.get(name)
            if idx is not None:
                keys.add(idx)
        return [i for i, k in enumerate(op.arg_keys) if k in keys]

    def _exchange_leaves(self, op, leaves) -> List[Tuple[int, Any]]:
        """The (index, leaf) pairs a due exchange for ``op`` covers: its
        declared halo operands that are actually decomposed."""
        return [(i, leaves[i]) for i in self._halo_leaf_indices(op)
                if self._is_sharded(leaves[i])]

    def _dispatch_exchange(self, r: Region, leaves: List[Tuple[int, Any]],
                           op: int) -> _Exchange:
        """Dispatch the halo exchange over ``leaves`` — asynchronously, and
        ALWAYS from the main thread.  Everything this executor runs on the
        mesh contains collectives (the exchange's permutes, and the
        collectives XLA SPMD inserts into partitioned compute), and
        collectives from concurrently-dispatching threads can interleave
        their per-device rendezvous in different orders and deadlock; a
        single dispatch thread gives every device the same enqueue order.
        The overlap schedules therefore dispatch here and hand the
        un-blocked result to the worker only to *wait* on.

        Per-device bytes: each APU sends ``w`` boundary slabs in each
        direction per decomposed dimension; a slab is the leaf's plane
        restricted to the APU's chunk of every *other* decomposed
        dimension — the surface-to-volume term a 2-D mesh shrinks.

        ``op`` is the index of the op the exchange serves: it tags the
        ``exchange`` span here and the worker's ``sync`` span."""
        widths = self._stencil_widths(r) or {}
        k = self.halo_multiplier
        t0 = time.perf_counter()
        outs: Dict[int, Any] = {}
        nbytes = 0
        with span(EXCHANGE, op=op):
            for i, x in leaves:
                asg = self._assignments(x.shape)
                items = []
                for d, ax, size in asg:
                    w = widths.get(ax, 0)
                    if w <= 0:
                        continue
                    local = x.shape[d] // size
                    items.append((d, min(k * w, local)))
                    if size > 1:
                        other = 1
                        for d2, _, size2 in asg:
                            if d2 != d:
                                other *= size2
                        plane = x.nbytes // x.shape[d] // other
                        nbytes += 2 * min(k * w, local) * plane
                if not items:
                    continue
                outs[i] = self._halo_region(r, tuple(items)).jitted(x)
        return _Exchange(outs, nbytes, t0, t0, op)

    def _finish_exchange(self, ex: _Exchange) -> _Exchange:
        """Wait for a dispatched exchange's transfers and close its wall
        interval (safe on the overlap worker: a pure wait, no dispatch).
        ``[t0, t1]`` is the in-flight window — the part of it intersecting
        compute spans is recorded as hidden (``overlap_s``)."""
        with span(SYNC, op=ex.op):
            jax.block_until_ready(list(ex.outs.values()))
        ex.t1 = time.perf_counter()
        return ex

    # -- interior/boundary split (split_stencil schedule) ----------------
    def _boundary_region(self, r: Region) -> Region:
        """The ``boundary(<row>)`` sub-region of stencil region ``r``
        (cached): recompute the region from its *exchanged* operands and
        blend only the ghost-adjacent band (shard-local index within the
        provisioned ghost depth of a shard edge) over the interior pass's
        result — the causal half of the interior/boundary split."""
        b = self._boundary_regions.get(r)
        if b is not None:
            return b
        widths = self._stencil_widths(r) or {}
        kmult = self.halo_multiplier
        assignments = self._assignments

        def boundary(interior, *args, **kwargs):
            full = r.fn(*args, **kwargs)

            def blend(i_leaf, f_leaf):
                shape = tuple(getattr(f_leaf, "shape", ()))
                if not shape:
                    return f_leaf
                mask = None
                for d, ax, size in assignments(shape):
                    w = widths.get(ax, 0)
                    if w <= 0 or size <= 1:
                        continue
                    local = shape[d] // size
                    depth = min(kmult * w, local)
                    idx = jax.lax.broadcasted_iota(
                        jnp.int32, shape, d) % local
                    m = (idx < depth) | (idx >= local - depth)
                    mask = m if mask is None else mask | m
                if mask is None:
                    return f_leaf
                return jnp.where(mask, f_leaf, i_leaf)

            return jax.tree.map(blend, interior, full)

        b = Region(name=f"boundary({self._row_name(r)})", fn=boundary,
                   offloaded=True, ledger=self._registry)
        self._boundary_regions[r] = b
        return b

    # -- Executor protocol -----------------------------------------------
    def run(self, target_region, *args, **kwargs):
        """Single calls fall back to the synchronous inner executor (host
        ledger); the decomposition only engages on whole programs."""
        return self._inner.run(target_region, *args, **kwargs)

    # -- exchange schedule -----------------------------------------------
    def _exchange_plan(self, prog: RegionProgram) -> List[bool]:
        """Which ops of this replay perform their halo exchange: every
        ``halo_multiplier``-th application of each stencil region
        (deterministic counters shared by the issue loop and the
        lookahead, persisted across replays so stepped replays
        amortize)."""
        plan = []
        for op in prog.ops:
            if self._stencil_widths(op.region) is None:
                plan.append(False)
                continue
            row = self._row_name(op.region)
            c = self._app_counts.get(row, 0)
            plan.append(c % self.halo_multiplier == 0)
            self._app_counts[row] = c + 1
        return plan

    def _record_exchange(self, r: Region, ex: _Exchange, spans) -> None:
        """Land one executed exchange on every per-device ledger (1/N
        shares): exchange seconds/bytes on the ``halo(<row>)`` row, plus
        the part of its wall interval that hid behind compute as
        ``overlap_s`` (excluded from totals by the ledger).  Its sync,
        which may have run on the overlap worker, is counted here, on the
        dispatching thread."""
        ov = min(interval_overlap(ex.t0, ex.t1, spans), ex.seconds)
        row = f"halo({self._row_name(r)})"
        nd = self.n_devices
        for led in self.ledgers:
            led.record(row, device=True, offloaded=True, compute_s=0.0,
                       exchange_s=ex.seconds / nd, exchange_bytes=ex.nbytes,
                       overlap_s=ov / nd)
            led.syncs += 1

    def _submit_lookahead(self, tp, prog, plan, k, resolve_placed
                          ) -> Optional[Tuple[int, Future]]:
        """AsyncExecutor's lookahead, composed with the decomposition:
        scan the next ``lookahead_depth`` ops for a due exchange whose
        halo operands are already resolvable (program inputs, constants,
        outputs of ops < k) and submit it on the overlap thread — it runs
        behind op ``k``'s interior compute.  Operands produced by op ``k``
        itself cannot be prefetched; their exchange is submitted at issue
        time instead (hiding behind their own op's compute)."""
        for j in range(k + 1, min(k + 1 + self.lookahead_depth,
                                  len(prog.ops))):
            if not plan[j]:
                continue
            op = prog.ops[j]
            idxs = set(self._halo_leaf_indices(op))
            if any(isinstance(d, Ref) and d.op >= k
                   for i, d in enumerate(op.leaves) if i in idxs):
                continue            # depends on an unfinished op: not ready
            leaves = self._exchange_leaves(
                op, [resolve_placed(d) if i in idxs else None
                     for i, d in enumerate(op.leaves)])
            if leaves:
                # dispatch HERE (main thread — single collective enqueue
                # order); the worker only waits out the transfer
                ex = self._dispatch_exchange(op.region, leaves, j)
                return (j, tp.submit(self._finish_exchange, ex))
            return None             # due but nothing decomposed: skip
        return None

    # -- program replay --------------------------------------------------
    def _verify_halo(self, prog: RegionProgram) -> None:
        """Pre-flight the program's halo declarations once per executor
        (static, no replay): an unresolvable ``halo_args`` entry or a
        halo_args-without-stencil region would make the exchange silently
        skip operands and corrupt the decomposed values — error-severity
        findings veto the replay.  Composed-reach findings are warnings
        (the wide-halo parity tests exercise those chains deliberately)
        and do not block."""
        if self._halo_verified.get(prog):
            return
        from repro.analysis import check_halo
        errors = check_halo(prog).errors
        if errors:
            raise ValueError(
                f"sharded replay of {prog.name!r} vetoed by halo "
                "verification:\n" + "\n".join(f"  {d}" for d in errors))
        self._halo_verified[prog] = True

    def replay_program(self, prog: RegionProgram, *inputs):
        self._verify_halo(prog)
        if self.overlap:
            with ThreadPoolExecutor(max_workers=1) as tp:
                return self._replay(prog, inputs, tp)
        return self._replay(prog, inputs, None)

    def _replay(self, prog: RegionProgram, inputs: tuple, tp):
        pol = self.policy
        stager = pol.stager
        selector = policy_selector(pol)
        staging = getattr(stager, "stages", False)
        nd = self.n_devices
        in_leaves = list(prog._input_leaves(inputs))
        if not staging:
            # unified node model: inputs scatter once and stay decomposed
            with span(PLACE):
                in_leaves = [self._place(x) if _is_array(x) else x
                             for x in in_leaves]
        env: List[List[Any]] = []
        resolve = _resolver(env, in_leaves)

        def resolve_placed(d):
            x = resolve(d)
            if staging or not _is_array(x):
                return x
            if isinstance(d, Lit):     # constants: scatter once, ever
                y = self._lit_cache.get(d)
                if y is None:
                    y = self._lit_cache[d] = self._place(x)
                return y
            return self._place(x)      # In/Ref leaves are already placed

        plan = self._exchange_plan(prog)
        pending: Optional[Tuple[int, Future]] = None
        spans: List[Tuple[float, float]] = []      # recent compute intervals

        def note_span(t0, t1):
            spans.append((t0, t1))
            if len(spans) > 8:
                del spans[0]

        for k, op in enumerate(prog.ops):
            r = op.region
            # every span of op k carries op=k, on the worker thread too
            with span(REGION, r.name, op=k) as op_span:
                with span(PLACE, op=k):
                    raw = [resolve_placed(d) for d in op.leaves]
                args, kwargs = jax.tree.unflatten(op.in_tree, raw)
                n = r.size_fn(args, kwargs)
                tgt = pol.router.target(r, args, kwargs, size=n)
                if pending is not None and pending[0] == k:
                    ex_fut: Optional[Future] = pending[1]
                    pending = None
                else:
                    ex_fut = None
                if tgt == "host":
                    op_span.set_metadata(target=tgt)
                    if ex_fut is not None:
                        # prefetched exchange for a host-routed call: the
                        # transfer happened; account it (with its overlap)
                        with span(EXCHANGE_WAIT, op=k):
                            ex = ex_fut.result()
                        self._record_exchange(r, ex, spans)
                    env.append(self._run_host(r, op, raw, n))
                    continue
                # variant selection happens here, per replayed call — the
                # captured trace stores Regions, so the same program runs
                # under any Selector at node scale too (XLA partitions
                # whichever variant's executable is chosen; resolve():
                # unknown -> ref)
                impl = r.resolve(selector.select(r, tgt, args, kwargs,
                                                 size=n))
                op_span.set_metadata(impl=impl, target=tgt)
                staging_s, staging_b = 0.0, 0
                acquired: list = []
                if staging and r.offloaded:
                    raw, staging_s, staging_b, acquired = \
                        self._stage_scatter(raw)
                    args, kwargs = jax.tree.unflatten(op.in_tree, raw)
                due = plan[k]
                ex: Optional[_Exchange] = None
                ex_leaves = self._exchange_leaves(op, raw) if due else []
                split = self.split_stencil and bool(ex_leaves)
                if due and ex_leaves and ex_fut is None and tp is None:
                    # sequential schedule: exchange first, compute consumes
                    # the exchanged operands (every exchange is exposed)
                    ex = self._finish_exchange(
                        self._dispatch_exchange(r, ex_leaves, k))
                if ex is not None and not split:
                    raw = list(raw)
                    for i, y in ex.outs.items():
                        raw[i] = y
                    args, kwargs = jax.tree.unflatten(op.in_tree, raw)
                exe = r.jitted_variant(impl, donate=False)
                t0 = time.perf_counter()
                # donate=False: sharded operands may be pool-staged or
                # reused by the exchange bookkeeping — donation is a
                # single-device executor optimization.  Under the
                # overlapped schedules this dispatch is the INTERIOR
                # compute: it consumes the un-exchanged (value-identical)
                # operands, so it never waits on the exchange running
                # behind it.
                with span(DISPATCH, op=k):
                    out = exe(*args, **kwargs)
                if due and ex_leaves and ex is None and ex_fut is None:
                    # this op's own exchange hides behind its own compute:
                    # dispatched on THIS thread right after the compute
                    # dispatch (ordered collectives), waited on by the
                    # worker
                    ex_fut = tp.submit(self._finish_exchange,
                                       self._dispatch_exchange(r, ex_leaves,
                                                               k))
                # submit the NEXT due exchange before blocking on this
                # compute — this ordering is the entire lookahead overlap
                # (operands staged per-call can't be prefetched across ops)
                if tp is not None and pending is None and not staging:
                    pending = self._submit_lookahead(tp, prog, plan, k,
                                                     resolve_placed)
                with span(SYNC, op=k):
                    jax.block_until_ready(out)
                t1 = time.perf_counter()
                note_span(t0, t1)
                if ex_fut is not None:
                    with span(EXCHANGE_WAIT, op=k):
                        ex = ex_fut.result()
                if split and ex is not None:
                    # causal boundary pass: recompute from exchanged
                    # operands, blend the ghost-adjacent band over the
                    # interior result
                    xraw = list(raw)
                    for i, y in ex.outs.items():
                        xraw[i] = y
                    xargs, xkwargs = jax.tree.unflatten(op.in_tree, xraw)
                    bregion = self._boundary_region(r)
                    tb0 = time.perf_counter()
                    with span(DISPATCH, op=k):
                        out = bregion.jitted(out, *xargs, **xkwargs)
                    with span(SYNC, op=k):
                        jax.block_until_ready(out)
                    tb1 = time.perf_counter()
                    note_span(tb0, tb1)
                    for led in self.ledgers:
                        led.record(f"boundary({self._row_name(r)})",
                                   device=True, offloaded=True,
                                   compute_s=(tb1 - tb0) / nd)
                        led.syncs += 1
                if staging and r.offloaded:
                    out, s, b = stager.stage_out(r, out, None)
                    staging_s += s
                    staging_b += b
                    for buf in acquired:          # staged operands are dead
                        self._device_pool.release(buf)
                else:
                    with span(PLACE, op=k):
                        out = jax.tree.map(
                            lambda x: self._place(x) if _is_array(x) else x,
                            out)
                row = self._row_name(r)
                for led in self.ledgers:
                    led.record(row, device=True, offloaded=r.offloaded,
                               compute_s=(t1 - t0) / nd,
                               staging_s=staging_s / nd,
                               staging_bytes=staging_b // nd,
                               elems=n // nd, impl=impl)
                    led.syncs += 1
                if ex is not None:
                    self._record_exchange(r, ex, spans)
                env.append(jax.tree.leaves(out))
        if pending is not None:       # trailing prefetch past a host turn
            with span(EXCHANGE_WAIT, op=pending[0]):
                pending[1].result()
        return jax.tree.unflatten(prog.out_tree,
                                  [resolve(d) for d in prog.out_leaves])

    def _run_host(self, r: Region, op, raw, n) -> list:
        """Adaptive small-problem path: gather operands to the host, run
        the host executable once, account on the node's host ledger."""
        host = [np.asarray(x) if _is_array(x) else x for x in raw]
        args, kwargs = jax.tree.unflatten(op.in_tree, host)
        impl = r.resolve(policy_selector(self.policy).select(
            r, "host", args, kwargs, size=n))
        t0 = time.perf_counter()
        out = r.executable("host", impl, donate=False)(*args, **kwargs)
        jax.block_until_ready(out)
        self.host_ledger.syncs += 1
        self.host_ledger.record(self._row_name(r), device=False,
                                offloaded=r.offloaded,
                                compute_s=time.perf_counter() - t0, elems=n,
                                impl=impl)
        return jax.tree.leaves(out)

    # -- accounting ------------------------------------------------------
    def reset_timings(self) -> None:
        for led in (*self.ledgers, self.host_ledger):
            led.reset_timings()

    def _device_summary(self, i: int, led: Ledger) -> dict:
        rows = list(led.regions.values())
        return {
            "device": i,
            "calls": sum(r.calls for r in rows),
            "compute_s": sum(r.compute_s for r in rows),
            "staging_s": sum(r.staging_s for r in rows),
            "exchange_s": sum(r.exchange_s for r in rows),
            "overlap_s": sum(r.overlap_s for r in rows),
            "staging_bytes": sum(r.staging_bytes for r in rows),
            "exchange_bytes": sum(r.exchange_bytes for r in rows),
            "elems": sum(r.host_elems + r.device_elems for r in rows),
        }

    def report(self) -> dict:
        """Node-level coverage: the per-device ledgers summed (which, by
        the 1/N-share recording convention, reproduces the measured wall
        split exactly) plus host-routed calls, with a ``per_device``
        compute/staging/exchange/overlap breakdown and the exchange
        schedule that produced it."""
        node = Ledger.merged((*self.ledgers, self.host_ledger),
                             name=self.mode)
        rep = node.coverage_report()
        rep["mode"] = self.mode
        rep["devices"] = self.n_devices
        rep["mesh_axis"] = self.axis
        rep["mesh_shape"] = list(self.axis_sizes)
        rep["schedule"] = self.schedule
        rep["halo_multiplier"] = self.halo_multiplier
        rep["per_device"] = [self._device_summary(i, led)
                             for i, led in enumerate(self.ledgers)]
        return rep


class ShardedProgram:
    """A captured program bound to its multi-APU executor: ``replay`` runs
    the decomposed trace, ``replay_batch`` scatters N independent instances
    across the APUs (data parallelism over the mesh axis), and
    ``coverage_report`` is the aggregated node view."""

    def __init__(self, prog: RegionProgram, executor: ShardExecutor):
        self.prog = prog
        self.executor = executor

    @property
    def mesh(self):
        return self.executor.mesh

    @property
    def ledgers(self) -> List[Ledger]:
        return self.executor.ledgers

    def replay(self, *inputs):
        return self.prog.replay(self.executor, *inputs)

    # the Executor protocol, so a ShardedProgram itself drops in where an
    # executor is expected (SimpleFoam.replay_steps, benchmarks)
    def replay_program(self, prog: RegionProgram, *inputs):
        return self.executor.replay_program(prog, *inputs)

    def run(self, target_region, *args, **kwargs):
        return self.executor.run(target_region, *args, **kwargs)

    def replay_batch(self, *stacked_inputs, in_axes=0):
        """Replay N stacked independent instances with the batch dimension
        scattered over the first mesh axis — each simulated APU decodes its
        own slice of the requests (the ``serve --mesh`` path)."""
        ex = self.executor
        mesh, axis, nd = ex.mesh, ex.axis, ex.n_devices
        n_axis = int(mesh.shape[axis])

        def scatter(x):
            if not _is_array(x) or not getattr(x, "ndim", 0):
                return x
            sh = shard_along_nd(mesh, {0: axis}, x.ndim) \
                if x.shape[0] % n_axis == 0 else replicated_sharding(mesh)
            return jax.device_put(x, sh)

        placed = jax.tree.map(scatter, stacked_inputs)
        t0 = time.perf_counter()
        out = self.prog.replay_batch(*placed, in_axes=in_axes)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        sizes = [int(a.size) for a in jax.tree.leaves(stacked_inputs)
                 if hasattr(a, "size")]
        for led in ex.ledgers:
            led.record(f"{self.prog.name}[batch]", device=True,
                       offloaded=True, compute_s=dt / nd,
                       elems=max(sizes, default=0) // nd)
        return out

    def coverage_report(self) -> dict:
        return self.executor.report()

    def report(self) -> dict:
        return self.executor.report()

    def reset_timings(self) -> None:
        self.executor.reset_timings()

    def summary(self) -> str:
        ex = self.executor
        halos = sum(1 for op in self.prog.ops
                    if ex._stencil_widths(op.region) is not None)
        shape = "x".join(str(s) for s in ex.axis_sizes)
        return (f"ShardedProgram({self.prog.name!r}: {len(self.prog)} ops, "
                f"{shape} decomposition on dims {ex.shard_dims}, "
                f"{halos} halo-exchanged ops, schedule={ex.schedule}, "
                f"halo_multiplier={ex.halo_multiplier}, "
                f"policy={ex.policy.name})")


def shard_program(prog: RegionProgram, mesh,
                  policy: Optional[ExecutionPolicy] = None, *,
                  axis=None, shard_dim=None, stencil_axis=None,
                  halo_multiplier: int = 1, overlap: bool = True,
                  split_stencil: bool = False,
                  lookahead_depth: int = 2) -> ShardedProgram:
    """Bind a captured program to a mesh of simulated APUs.

        mesh = make_apu_mesh(4)          # repro.launch.mesh; (2, 2) for 2-D
        sp = shard_program(prog, mesh, DiscretePolicy(),
                           halo_multiplier=2)      # wide-halo: 1/2 the syncs
        out = sp.replay(*inputs)
        sp.coverage_report()["per_device"]     # compute/staging/exchange

    ``overlap`` (default) hides exchanges behind interior compute;
    ``split_stencil`` runs the causal interior/boundary split;
    ``halo_multiplier=k`` exchanges ``k``-wide ghosts every ``k``-th
    application (docs/SCALING.md)."""
    return ShardedProgram(prog, ShardExecutor(
        policy, mesh, axis=axis, shard_dim=shard_dim,
        stencil_axis=stencil_axis, halo_multiplier=halo_multiplier,
        overlap=overlap, split_stencil=split_stencil,
        lookahead_depth=lookahead_depth))
