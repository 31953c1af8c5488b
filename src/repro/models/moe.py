"""Mixture-of-Experts MLP: top-k routing with sort-based capacity dispatch,
plus host-resident expert paging for oversubscribed decode.

TPU-native formulation (no per-token weight gathers): flatten the (token,
expert-choice) pairs, stable-sort by expert id, rank within expert segment by
a cumsum trick, scatter into a dense ``[E, C, d]`` buffer, run both expert
matmuls as batched einsums (sharded over the ``experts`` -> ``model`` mesh
axis = expert parallelism), gather back and combine with router weights.
Tokens beyond an expert's capacity ``C = ceil(T*k/E * cf)`` are dropped
(standard capacity-factor semantics; cf default 1.25).

``moe_ref`` is the O(T*E) oracle used by tests.

:class:`ExpertPager` + :func:`moe_decode_paged` are the oversubscription
path (ROADMAP item 4 / ``repro.core.oversub``): the stacked expert weights
live in host DRAM and only the experts the router actually selects are
paged into an LRU device-resident working set bounded by a
``MemoryBudget`` — a qwen3-30B-style model whose experts dwarf device
memory decodes by paying per-token expert fetches instead of OOMing.
Compute order is fixed (ascending expert id, f32 accumulate), so the
budgeted run is bit-identical to the everything-resident run — placement
never changes values.

The pager also runs a one-slab staging lookahead mirroring
:class:`~repro.core.program.AsyncExecutor`: while expert ``i`` computes,
a single background thread fetches expert ``i+1``'s slab
(:meth:`ExpertPager.prefetch`), and the fetch-behind-compute overlap is
accounted with the same :func:`~repro.core.program.interval_overlap`
arithmetic the async executor uses (``stats.prefetch_overlap_s``, plus
the ``moe_prefetch_overlap_s`` ledger gauge when a ledger is passed).
Prefetch changes *when* a slab moves, never *what* is computed — the
bit-parity claim above is untouched.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.core import umem
from repro.core.program import interval_overlap
from repro.core.umem import MemSpace
from repro.models.layers import ParamSpec, noshard


def moe_specs(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, pd = cfg.d_model, cfg.param_dtype
    s = {
        "router": ParamSpec((d, m.n_experts), ("embed", "experts"), "float32"),
        "wi_gate": ParamSpec((m.n_experts, d, m.d_ff), ("experts", "embed", "moe_ff"), pd),
        "wi_up": ParamSpec((m.n_experts, d, m.d_ff), ("experts", "embed", "moe_ff"), pd),
        "wo": ParamSpec((m.n_experts, m.d_ff, d), ("experts", "moe_ff", "embed"), pd),
    }
    if m.shared_expert_ff:
        f = m.shared_expert_ff
        s["shared"] = {
            "wi_gate": ParamSpec((d, f), ("embed", "ff"), pd),
            "wi_up": ParamSpec((d, f), ("embed", "ff"), pd),
            "wo": ParamSpec((f, d), ("ff", "embed"), pd),
        }
    return s


def _router(p, x2, m: MoEConfig):
    """x2 [T, d] -> (gate_weights [T,k], expert_ids [T,k], aux_loss)."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    T, E = logits.shape
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(density * mean_probs)
    return gate, idx, aux


def _capacity(T: int, m: MoEConfig) -> int:
    c = int(T * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8 lanes


def _largest_divisor(T: int, G: int) -> int:
    while G > 1 and T % G:
        G -= 1
    return max(G, 1)


def moe_mlp(p, x, cfg: ModelConfig, shd=noshard, n_groups: int = 16):
    """x [B, S, d] -> (y [B, S, d], aux_loss).

    GROUP-LOCAL dispatch (beyond-paper perf iteration, docs/EXPERIMENTS.md SPerf):
    tokens are split into G groups aligned with the data shards; routing,
    ranking and the capacity scatter/gather are all per-group (batched, so
    SPMD partitions them along G with no cross-shard collectives), and the
    only inter-shard movement left is the (G x E) buffer resharding for the
    expert matmuls — a proper all-to-all of token payloads instead of the
    global-argsort path's full-buffer all-reduces.
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    G = _largest_divisor(T, n_groups)
    Tg = T // G
    C = _capacity(Tg, m)

    xg = shd(x.reshape(G, Tg, d), "expert_group", None, None)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)              # [G,Tg,k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(idx[..., 0], E), axis=(0, 1))
    aux = E * jnp.sum(density * jnp.mean(probs, axis=(0, 1)))

    fe = idx.reshape(G, Tg * k)                      # expert id per pair
    ft = jnp.repeat(jnp.arange(Tg)[None], G, 0).reshape(G, Tg, 1)
    ft = jnp.broadcast_to(jnp.arange(Tg)[None, :, None], (G, Tg, k)) \
        .reshape(G, Tg * k)
    grp = lambda t: shd(t, "expert_group", None)     # keep SPMD on the G axis
    order = grp(jnp.argsort(fe, axis=1, stable=True))
    se = grp(jnp.take_along_axis(fe, order, axis=1))
    st = grp(jnp.take_along_axis(ft, order, axis=1))
    counts = jnp.sum(jax.nn.one_hot(fe, E, dtype=jnp.int32), axis=1)  # [G,E]
    seg_start = jnp.cumsum(counts, axis=1) - counts
    rank = grp(jnp.arange(Tg * k)[None]
               - jnp.take_along_axis(seg_start, se, axis=1))
    keep = rank < C
    dst = grp(jnp.where(keep, se * C + rank, E * C))  # [G, Tg*k]

    def scatter_one(xg_, st_, dst_, keep_):
        upd = jnp.where(keep_[:, None], xg_[st_], 0)
        return jnp.zeros((E * C + 1, d), x.dtype).at[dst_].set(upd)

    buf = jax.vmap(scatter_one)(xg, st, dst, keep)   # [G, E*C+1, d]
    h = buf[:, : E * C].reshape(G, E, C, d)
    h = shd(h, "expert_group", "experts", None, None)
    g_ = jnp.einsum("gecd,edf->gecf", h, p["wi_gate"])
    u = jnp.einsum("gecd,edf->gecf", h, p["wi_up"])
    o = jax.nn.silu(g_.astype(jnp.float32)).astype(x.dtype) * u
    o = jnp.einsum("gecf,efd->gecd", o, p["wo"])
    o = shd(o, "expert_group", "experts", None, None)

    def gather_one(o_, dst_, st_, gate_s):
        o_flat = jnp.concatenate([o_.reshape(E * C, d),
                                  jnp.zeros((1, d), x.dtype)], 0)
        per_pair = o_flat[dst_].astype(jnp.float32) * gate_s[:, None]
        return jnp.zeros((Tg, d), jnp.float32).at[st_].add(per_pair)

    gate_sorted = grp(jnp.take_along_axis(gate.reshape(G, Tg * k), order,
                                          axis=1))
    yg = jax.vmap(gather_one)(o, dst, st, gate_sorted)   # [G,Tg,d] f32
    yg = shd(yg.astype(x.dtype), "expert_group", None, None)
    y = yg.reshape(B, S, d)
    y = shd(y, "batch", None, None)

    if m.shared_expert_ff:
        sp = p["shared"]
        sg = jnp.einsum("btd,df->btf", x, sp["wi_gate"])
        su = jnp.einsum("btd,df->btf", x, sp["wi_up"])
        sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x.dtype) * su
        y = y + jnp.einsum("btf,fd->btd", sh, sp["wo"])
    return y, aux


def moe_ref(p, x, cfg: ModelConfig):
    """O(T*E) dense oracle: every expert on every token, masked combine.
    No capacity drops — tests compare against moe_mlp with cf large enough
    that nothing drops."""
    m = cfg.moe
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    gate, idx, aux = _router(p, x2, m)
    g = jnp.einsum("td,edf->tef", x2, p["wi_gate"])
    u = jnp.einsum("td,edf->tef", x2, p["wi_up"])
    o = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    o = jnp.einsum("tef,efd->ted", o, p["wo"])       # [T,E,d]
    mask = jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32)  # [T,k,E]
    w = (mask * gate[..., None]).sum(1)              # [T,E]
    y = jnp.einsum("ted,te->td", o.astype(jnp.float32), w).astype(x.dtype)
    y = y.reshape(B, S, d)
    if m.shared_expert_ff:
        sp = p["shared"]
        sg = jnp.einsum("btd,df->btf", x.reshape(B, S, d), sp["wi_gate"])
        su = jnp.einsum("btd,df->btf", x.reshape(B, S, d), sp["wi_up"])
        sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x.dtype) * su
        y = y + jnp.einsum("btf,fd->btd", sh, sp["wo"])
    return y, aux


# ---------------------------------------------------------------------------
# Host-resident expert paging (oversubscribed decode)
# ---------------------------------------------------------------------------

#: the stacked per-expert weight matrices the pager slices slabs from
EXPERT_KEYS = ("wi_gate", "wi_up", "wo")


@dataclasses.dataclass
class PagingStats:
    fetches: int = 0                # host -> device expert slab moves
    hits: int = 0                   # expert already device-resident
    evictions: int = 0              # LRU slabs dropped to fit the budget
    bytes_fetched: int = 0
    prefetch_hits: int = 0          # fetches satisfied by the lookahead
    prefetch_overlap_s: float = 0.0  # fetch time hidden behind compute

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ExpertPager:
    """LRU device-resident working set of expert weight slabs over
    host-resident stacks, bounded by a
    :class:`~repro.core.oversub.MemoryBudget`.

    The stacked ``wi_gate``/``wi_up``/``wo`` parameters (``[E, ...]``) are
    parked in host DRAM through the placement axis; :meth:`get` pages one
    expert's slab (``wi_gate [d,f]``, ``wi_up [d,f]``, ``wo [f,d]``) to
    the device on demand and evicts least-recently-used slabs until the
    working set fits the budget again.  The tiny router matrix stays
    device-resident — routing must run before the pager knows which
    experts the token needs.  On the CPU container the host/device moves
    are logical (docs/DESIGN.md §2); the claim structure — budget-bounded
    resident high-water, fetch/hit/eviction counts, bit-parity with the
    resident run — is what the tests assert."""

    def __init__(self, p, cfg: ModelConfig, budget=None,
                 host_space: Optional[MemSpace] = None,
                 lookahead: bool = True):
        m = cfg.moe
        self.n_experts = m.n_experts
        self.budget = budget
        host = host_space or umem.preferred_host_space()
        self.router = p["router"]              # device-resident by design
        self.shared = p.get("shared")
        # one host array per expert slab, sliced while still on the device:
        # a fetch then moves exactly one slab (slicing a host-space stack
        # is itself a computation on host memory)
        self._host = {k: [umem.place(p[k][e], host) if host is not None
                          else p[k][e] for e in range(p[k].shape[0])]
                      for k in EXPERT_KEYS}
        self.slab_bytes = sum(int(p[k][0].nbytes) for k in EXPERT_KEYS)
        self._resident: Dict[int, dict] = {}   # expert id -> slab (LRU order)
        self.stats = PagingStats()
        self.lookahead = lookahead
        self._lock = threading.Lock()
        self._pending: Dict[int, object] = {}  # expert id -> Future
        self._pf_pool = None                   # created on first prefetch

    @property
    def footprint_bytes(self) -> int:
        """Device bytes an everything-resident run would pin — the
        numerator of the oversubscription ratio."""
        return self.slab_bytes * self.n_experts

    @property
    def resident_bytes(self) -> int:
        return self.slab_bytes * len(self._resident)

    def _fetch_slab(self, e: int) -> tuple:
        """Page expert ``e`` device-ward; returns (slab, t0, t1) with the
        materialized fetch interval (the span overlap accounting uses)."""
        t0 = time.perf_counter()
        slab = {k: umem.place(self._host[k][e], MemSpace.DEVICE)
                for k in EXPERT_KEYS}
        for v in slab.values():
            jax.block_until_ready(v)
        return slab, t0, time.perf_counter()

    def prefetch(self, e: int) -> None:
        """Hint that expert ``e`` is needed next: start fetching its slab
        on the single staging thread while the caller computes the current
        expert (one-step lookahead — AsyncExecutor's contract applied to
        expert slabs).  No-op when the slab is resident, already in
        flight, or ``lookahead`` is off.  Budget charging and eviction
        happen when :meth:`get` installs the slab, so the one in-flight
        slab is the only budget slack the lookahead adds — the same
        next-bank allowance AsyncExecutor's double buffer carries."""
        e = int(e)
        if not self.lookahead:
            return
        with self._lock:
            if e in self._resident or e in self._pending:
                return
            if self._pf_pool is None:
                self._pf_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="expert-prefetch")
            self._pending[e] = self._pf_pool.submit(self._fetch_slab, e)

    def get(self, e: int, compute_spans=None) -> dict:
        """The device-resident slab of expert ``e``, fetching and evicting
        as the budget requires.  A slab arriving via :meth:`prefetch`
        still counts as a fetch (the bytes moved); the time its fetch hid
        behind the caller's ``compute_spans`` intervals accrues to
        ``stats.prefetch_overlap_s``."""
        e = int(e)
        with self._lock:
            slab = self._resident.pop(e, None)
            if slab is not None:
                self._resident[e] = slab       # re-insert = LRU touch
                self.stats.hits += 1
                return slab
            fut = self._pending.pop(e, None)
        if fut is not None:
            slab, t0, t1 = fut.result()
            self.stats.prefetch_hits += 1
            if compute_spans:
                self.stats.prefetch_overlap_s += interval_overlap(
                    t0, t1, compute_spans)
        else:
            slab, _, _ = self._fetch_slab(e)
        with self._lock:
            self._resident[e] = slab
            self.stats.fetches += 1
            self.stats.bytes_fetched += self.slab_bytes
            if self.budget is not None:
                self.budget.charge(self.slab_bytes)
                # shed LRU slabs until we fit again — but never the slab
                # the caller is about to compute with
                while self.budget.over and len(self._resident) > 1:
                    victim = next(iter(self._resident))
                    if victim == e:
                        break
                    self._resident.pop(victim)
                    self.budget.release(self.slab_bytes)
                    self.stats.evictions += 1
        return slab

    def drop(self) -> None:
        """Release the whole resident set (end of a decode stream)."""
        with self._lock:
            pending = list(self._pending.values())
            self._pending.clear()
        for fut in pending:
            fut.cancel()                       # running fetches just expire
        if self.budget is not None:
            self.budget.release(self.resident_bytes)
        self._resident.clear()


def moe_decode_paged(pager: ExpertPager, x, cfg: ModelConfig, ledger=None):
    """x [B, S, d] -> (y [B, S, d], aux_loss), computing only the experts
    the router selects, each through :meth:`ExpertPager.get`.

    Dense per-expert compute over all T tokens (decode-sized T makes that
    cheap) with a FIXED accumulation order — ascending expert id, f32
    accumulate, per-token gate mask — so the output is a pure function of
    the values, not of which slabs happened to be resident: budgeted and
    unbudgeted runs are bit-identical.  Matches ``moe_ref`` to tolerance
    (its lane order differs), which the tests also pin.

    Before computing expert ``i`` the loop prefetches expert ``i+1``
    (ascending order is fixed, so the lookahead is exact, not a guess);
    each expert's compute interval is recorded so the pager can account
    how much of the next fetch hid behind it.  With a ``ledger``, the
    cumulative hidden time lands on the ``moe_prefetch_overlap_s``
    gauge."""
    m = cfg.moe
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    gate, idx, aux = _router({"router": pager.router}, x2, m)
    gate_np = np.asarray(gate)                 # [T,k] f32
    idx_np = np.asarray(idx)                   # [T,k]
    y = jnp.zeros((B * S, d), jnp.float32)
    experts = sorted({int(v) for v in idx_np.ravel()})
    hits0 = pager.stats.prefetch_hits
    spans = []                       # compute intervals the fetches hide in
    for i, e in enumerate(experts):
        if i + 1 < len(experts):
            pager.prefetch(experts[i + 1])
        w = pager.get(e, compute_spans=spans)
        t0 = time.perf_counter()
        we = jnp.asarray((gate_np * (idx_np == e)).sum(-1), jnp.float32)
        g = jnp.einsum("td,df->tf", x2, w["wi_gate"])
        u = jnp.einsum("td,df->tf", x2, w["wi_up"])
        o = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        o = jnp.einsum("tf,fd->td", o, w["wo"])
        y = jax.block_until_ready(y + o.astype(jnp.float32) * we[:, None])
        spans.append((t0, time.perf_counter()))
    y = y.astype(x.dtype).reshape(B, S, d)
    if ledger is not None:
        ledger.serve_gauge("moe_prefetch_overlap_s",
                           pager.stats.prefetch_overlap_s)
        new_hits = pager.stats.prefetch_hits - hits0
        if new_hits:
            ledger.serve_record("moe_prefetch_hit", new_hits)
    if m.shared_expert_ff and pager.shared is not None:
        sp = pager.shared
        sg = jnp.einsum("btd,df->btf", x, sp["wi_gate"])
        su = jnp.einsum("btd,df->btf", x, sp["wi_up"])
        sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x.dtype) * su
        y = y + jnp.einsum("btf,fd->btd", sh, sp["wo"])
    return y, aux
