"""Paged KV cache — the serving engine's parking store (paper C1 + C4).

Between PREFILL and slot admission a request's KV cache is *paged*: the
``k``/``v``-keyed leaves (the :data:`~repro.launch.serve.KV_PLACE_KEYS`
role keying of :class:`~repro.launch.serve.KVCachePlacer`) are split along
the token axis into fixed-size pages copied into pooled buffers from a
:class:`~repro.core.pool.DeviceBufferPool`; everything else (slot
positions, recurrent state) rides along as a dense residual tree.  Pages
recycle through the pool's free-list (paper C4: Umpire-style reuse instead
of alloc/free churn), and two budgets bound the store:

* ``device_budget_bytes`` — when device-resident page bytes exceed it, the
  least-recently-used entry's pages *spill* to host DRAM through the
  placement axis (:func:`~repro.core.umem.place` into
  ``preferred_host_space()``), so the cache can exceed device memory —
  the paper's incremental-offload pattern applied to serving.  Spilled
  pages are fetched back through the same axis at admission; placement
  never changes values, so parity survives oversubscription.
* ``total_budget_bytes`` — when even host spill cannot hold the store,
  whole LRU entries are *evicted* (pages freed, the scheduler re-queues
  the request for a fresh prefill).

The CPU backend exposes ``device``, ``pinned_host`` and ``unpinned_host``
memory kinds too, so spilled pages really change space there; what the
tests and ``fig_traffic`` assert is the claim structure (budget-bounded
device high-water, spill counts, bit-parity across the spill).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pool import DeviceBufferPool
from repro.core.umem import (MemSpace, device_operands, place,
                             preferred_host_space, space_of)
from repro.launch.serve import KV_PLACE_KEYS

DEFAULT_PAGE_TOKENS = 8


@functools.partial(jax.jit, donate_argnums=(1,))
@device_operands
def _copy_into(src, dst):
    """Donating full overwrite: the result owns ``dst``'s (pooled) storage
    and carries ``src``'s values — how jax 'reuses' an immutable buffer."""
    return jnp.where(True, src, dst)


def _leaf_role(path) -> Optional[str]:
    """The KV role of a tree path (``"k"``/``"v"``) or None — the same
    role keying :func:`repro.launch.serve.place_kv_leaves` uses."""
    for p in path:
        key = getattr(p, "key", None)
        if key in KV_PLACE_KEYS:
            return key
    return None


def _token_axis(path) -> int:
    """Token axis of a k/v leaf: cache_specs stacks repeated cycle layers
    (leaves under a ``cycles`` key gain a leading layer axis, [L, B, S,
    ...]) while ``rest*`` layers stay per-layer ([B, S, ...])."""
    for p in path:
        if getattr(p, "key", None) == "cycles":
            return 2
    return 1


@dataclasses.dataclass
class PagedKVStats:
    pages_committed: int = 0
    pages_released: int = 0
    pages_spilled: int = 0          # device -> host placement-axis moves
    pages_fetched: int = 0          # host -> device, paid at admission
    evictions: int = 0              # whole entries dropped (total budget)
    device_bytes: int = 0           # page bytes logically device-resident
    host_bytes: int = 0             # page bytes logically host-resident
    device_high_water_bytes: int = 0
    total_high_water_bytes: int = 0
    role_pages: Dict[str, int] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _Entry:
    """One parked request: paged k/v leaves + dense residual leaves, in
    tree-flatten order so ``treedef.unflatten`` reconstructs the cache."""
    req_id: int
    treedef: object
    leaves: List[Tuple]             # ("page", pages, shape, valid, axis) | ("dense", leaf)
    page_bytes: int
    last_touch: int
    on_host: bool = False


class PagedKVCache:
    """Fixed-size KV pages over a :class:`DeviceBufferPool` free-list with
    LRU host spill and whole-entry eviction (module docstring)."""

    def __init__(self, page_tokens: int = DEFAULT_PAGE_TOKENS,
                 pool: Optional[DeviceBufferPool] = None,
                 device_budget_bytes: Optional[int] = None,
                 total_budget_bytes: Optional[int] = None,
                 host_space: Optional[MemSpace] = None,
                 budget=None):
        if page_tokens < 1:
            raise ValueError("page_tokens must be >= 1")
        self.page_tokens = page_tokens
        # min_elems=0: every page pools — smoke-scale pages are far below
        # the paper's 5K-element threshold, and the free-list IS the point
        self.pool = pool if pool is not None else DeviceBufferPool(min_elems=0)
        self.device_budget_bytes = device_budget_bytes
        self.total_budget_bytes = total_budget_bytes
        self.host_space = host_space or preferred_host_space()
        # a MemoryBudget (repro.core.oversub) is the oversubscription form
        # of device_budget_bytes: its limit caps device-resident page bytes
        # (tightest of the two wins) and the store mirrors its device-byte
        # deltas into it, so one budget instance can span the KV store and
        # other device consumers.  Don't ALSO hand the same budget to
        # self.pool — that would double-charge every page.
        self.budget = budget
        self.stats = PagedKVStats()
        self._entries: Dict[int, _Entry] = {}
        self._clock = 0

    def _device_limit(self) -> Optional[int]:
        lims = [b for b in (self.device_budget_bytes,
                            getattr(self.budget, "limit_bytes", None))
                if b is not None]
        return min(lims) if lims else None

    def _device_delta(self, nbytes: int) -> None:
        """Mirror a device-resident byte change into the attached budget
        (charge on +, release on −); pressure events mark the window
        between a commit landing over the limit and the LRU spill that
        sheds it."""
        if self.budget is None or nbytes == 0:
            return
        if nbytes > 0:
            self.budget.charge(nbytes)
        else:
            self.budget.release(-nbytes)

    # -- bookkeeping ---------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, req_id: int) -> bool:
        return req_id in self._entries

    @property
    def total_bytes(self) -> int:
        return self.stats.device_bytes + self.stats.host_bytes

    def touch(self, req_id: int) -> None:
        e = self._entries.get(req_id)
        if e is not None:
            self._clock += 1
            e.last_touch = self._clock

    def _lru(self, *, exclude: Optional[int] = None,
             on_host: Optional[bool] = None) -> Optional[_Entry]:
        best = None
        for e in self._entries.values():
            if e.req_id == exclude:
                continue
            if on_host is not None and e.on_host != on_host:
                continue
            if best is None or e.last_touch < best.last_touch:
                best = e
        return best

    def _water_marks(self) -> None:
        s = self.stats
        s.device_high_water_bytes = max(s.device_high_water_bytes,
                                        s.device_bytes)
        s.total_high_water_bytes = max(s.total_high_water_bytes,
                                       s.device_bytes + s.host_bytes)

    # -- commit: cache tree -> pages -----------------------------------
    def _page_leaf(self, leaf, true_len: int, axis: int):
        """Split one k/v leaf along its token axis into fixed-size pooled
        pages covering ``min(true_len, S)`` tokens (the ring-slot clamp: a
        local-attention cache has S = window slots); the untouched tail is
        zeros by construction (init_cache) and is re-padded exactly at
        gather."""
        if space_of(leaf) != MemSpace.DEVICE.kind:
            # slicing is a computation, and the TPU computes only on device
            # memory: an offloaded (host-placed) cache is fetched first
            leaf = place(leaf, MemSpace.DEVICE)
        S = leaf.shape[axis]
        valid = min(max(int(true_len), 1), S)
        pt = self.page_tokens
        n_pages = -(-valid // pt)
        page_shape = leaf.shape[:axis] + (pt,) + leaf.shape[axis + 1:]
        pages = []
        for p in range(n_pages):
            chunk = jax.lax.slice_in_dim(leaf, p * pt,
                                         min((p + 1) * pt, S), axis=axis)
            if chunk.shape[axis] < pt:
                pad = [(0, 0)] * leaf.ndim
                pad[axis] = (0, pt - chunk.shape[axis])
                chunk = jnp.pad(chunk, pad)
            buf = self.pool.acquire(page_shape, leaf.dtype)
            pages.append(_copy_into(chunk, buf))
        return pages, leaf.shape, valid

    def commit(self, req_id: int, cache, true_len: int) -> List[int]:
        """Park a prefilled cache: page the k/v leaves, keep the rest
        dense.  Returns the req_ids of any entries the total budget forced
        out (the scheduler re-queues them as EVICTED)."""
        if req_id in self._entries:
            raise ValueError(f"request {req_id} already committed")
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        leaves: List[Tuple] = []
        page_bytes = 0
        n_pages = 0
        for path, leaf in flat:
            role = _leaf_role(path)
            axis = _token_axis(path)
            if role is not None and getattr(leaf, "ndim", 0) > axis:
                pages, shape, valid = self._page_leaf(leaf, true_len, axis)
                leaves.append(("page", pages, shape, valid, axis))
                page_bytes += sum(int(p.nbytes) for p in pages)
                n_pages += len(pages)
                self.stats.role_pages[role] = \
                    self.stats.role_pages.get(role, 0) + len(pages)
            else:
                leaves.append(("dense", leaf))
        self._clock += 1
        self._entries[req_id] = _Entry(req_id=req_id, treedef=treedef,
                                       leaves=leaves, page_bytes=page_bytes,
                                       last_touch=self._clock)
        self.stats.pages_committed += n_pages
        self.stats.device_bytes += page_bytes
        self._device_delta(page_bytes)
        self._water_marks()
        self._spill_to_budget()
        return self._evict_to_budget(newest=req_id)

    # -- budgets: LRU spill, then LRU eviction -------------------------
    def _spill_entry(self, e: _Entry) -> None:
        if self.host_space is None or e.on_host:
            return
        n = 0
        for i, rec in enumerate(e.leaves):
            if rec[0] == "page":
                _, pages, shape, valid, axis = rec
                pages = [place(p, self.host_space) for p in pages]
                e.leaves[i] = ("page", pages, shape, valid, axis)
                n += len(pages)
        e.on_host = True
        self.stats.pages_spilled += n
        self.stats.device_bytes -= e.page_bytes
        self.stats.host_bytes += e.page_bytes
        self._device_delta(-e.page_bytes)
        self._water_marks()

    def _spill_to_budget(self) -> None:
        limit = self._device_limit()
        if limit is None or self.host_space is None:
            return
        while self.stats.device_bytes > limit:
            victim = self._lru(on_host=False)
            if victim is None:
                break
            self._spill_entry(victim)

    def _evict_to_budget(self, newest: int) -> List[int]:
        evicted: List[int] = []
        if self.total_budget_bytes is None:
            return evicted
        while self.total_bytes > self.total_budget_bytes \
                and len(self._entries) > 1:
            victim = self._lru(exclude=newest)
            if victim is None:
                break
            self.free(victim.req_id)
            self.stats.evictions += 1
            evicted.append(victim.req_id)
        return evicted

    # -- gather: pages -> cache tree (admission) -----------------------
    def gather(self, req_id: int):
        """Reassemble and remove a parked cache.  Spilled pages pay the
        host->device crossing here (placement axis); page buffers return
        to the pool free-list for the next commit."""
        e = self._entries.pop(req_id)
        if e.on_host:
            self.stats.host_bytes -= e.page_bytes
        else:
            self.stats.device_bytes -= e.page_bytes
            self._device_delta(-e.page_bytes)
        out = []
        for rec in e.leaves:
            if rec[0] == "dense":
                out.append(rec[1])
                continue
            _, pages, shape, valid, axis = rec
            if e.on_host:
                pages = [place(p, MemSpace.DEVICE) for p in pages]
                self.stats.pages_fetched += len(pages)
            full = jax.lax.slice_in_dim(jnp.concatenate(pages, axis=axis),
                                        0, valid, axis=axis)
            S = shape[axis]
            if valid < S:
                pad = [(0, 0)] * len(shape)
                pad[axis] = (0, S - valid)
                full = jnp.pad(full, pad)
            out.append(full)
            for p in pages:
                self.pool.release(p)
            self.stats.pages_released += len(pages)
        return jax.tree_util.tree_unflatten(e.treedef, out)

    def free(self, req_id: int) -> None:
        """Drop a parked cache without gathering (eviction, abort)."""
        e = self._entries.pop(req_id, None)
        if e is None:
            return
        if e.on_host:
            self.stats.host_bytes -= e.page_bytes
        else:
            self.stats.device_bytes -= e.page_bytes
            self._device_delta(-e.page_bytes)
        for rec in e.leaves:
            if rec[0] == "page":
                for p in rec[1]:
                    self.pool.release(p)
                self.stats.pages_released += len(rec[1])
