"""Slot-based continuous-batching scheduler on the captured region programs.

The engine runs ON the PR-5 serving spine, not beside it:

* admission prefills ride the captured ``PREFILL`` + ``KV_APPEND`` program
  (:func:`repro.launch.serve.capture_prefill_program`), one program per
  prompt-length bucket (length-bucketed admission — capture freezes
  shapes, so each distinct prompt length owns one captured program that
  every request of that length replays);
* the decode tick is ONE captured program per engine: ``DECODE_SLOTS`` —
  the ``DECODE_STEP`` region body (``impl_fn("ref")``) vmapped over the
  slot axis with a *per-slot position vector as a program input* (the
  static decode program freezes positions as constants; ragged requests
  need them live) — followed by the same ``KV_APPEND`` commit, where the
  policy's placement axis re-homes the appended pages (``--offload-kv``);
* ``SLOT_ADMIT`` scatters an admitted request's gathered cache into its
  slot row of the stacked slot cache — a region, so admission traffic is
  accounted like everything else.

The active-mask over slots is split between program and host: inside
``DECODE_SLOTS`` inactive slots keep their previous token (``jnp.where``
on the mask — the emitted value is exactly the solo value for active
slots), and the host-side scheduler commits results only for active slots.
Inactive slots still compute (the program is frozen-shape; that waste is
the occupancy story ``fig_traffic`` reports) and garbage-write their own
slot row, which the next ``SLOT_ADMIT`` fully overwrites — rows never
leak across the vmapped slot axis.

Per-request state machine: QUEUED -> PREFILL (prefilled, KV parked in the
:class:`~repro.serve.paged_kv.PagedKVCache`) -> DECODE (in a slot) ->
DONE, with EVICTED on the budget path (pages dropped, request re-queued
for a fresh prefill).  Every decision lands on the shared
:class:`~repro.core.ledger.Ledger` (``serve`` section of
``coverage_report()``).

Parity contract (asserted by tests and ``fig_traffic``): each request's
token sequence is bit-identical to a solo jit decode of the same prompt —
vmap over the slot axis is bit-stable on this backend (the same invariant
``replay_batch`` already asserts), placement never changes values, and
active slots pass through ``jnp.where(True, new, old)`` unchanged.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.program import capture
from repro.core.regions import region
from repro.launch.serve import capture_prefill_program, make_serve_regions
from repro.models import transformer as T
from repro.serve.paged_kv import PagedKVCache

QUEUED = "QUEUED"
PREFILL = "PREFILL"
DECODE = "DECODE"
DONE = "DONE"
EVICTED = "EVICTED"

#: legal transitions of the per-request state machine
_TRANSITIONS = {
    QUEUED: (PREFILL, DONE),            # gen==1 finishes at prefill
    PREFILL: (DECODE, EVICTED),
    DECODE: (DONE,),
    EVICTED: (QUEUED,),                 # re-queued for a fresh prefill
    DONE: (),
}


@dataclasses.dataclass
class Request:
    """One sequence moving through the engine."""
    req_id: int
    prompt: np.ndarray                  # [prompt_len] int32 token ids
    gen: int                            # tokens to generate (incl. prefill's)
    arrival_tick: int = 0
    state: str = QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    submit_time: float = 0.0
    slot: Optional[int] = None
    pos: int = 0                        # next decode position
    evictions: int = 0
    history: List[str] = dataclasses.field(default_factory=lambda: [QUEUED])

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def done(self) -> bool:
        return self.state == DONE


def batch_for_prompt(cfg, prompt: np.ndarray) -> dict:
    """Batch-1 prefill inputs for one prompt (mirrors the driver's
    ``_prefill_inputs`` for arbitrary single prompts)."""
    prompt_len = int(prompt.shape[0])
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
    if cfg.mrope_sections is not None:
        pos = jnp.arange(prompt_len, dtype=jnp.int32)[None, :, None]
        batch["positions3"] = jnp.broadcast_to(pos, (1, prompt_len, 3))
    if cfg.n_enc_layers:
        batch["enc_embeds"] = jnp.zeros(
            (1, cfg.enc_len, cfg.d_model), cfg.compute_dtype)
    return batch


class ServeEngine:
    """Continuous-batching engine: N decode slots over one captured tick
    program, paged-KV parking between prefill and admission (module
    docstring)."""

    def __init__(self, cfg, mesh, params, executor, *, max_len: int,
                 n_slots: int = 4, kv: Optional[PagedKVCache] = None,
                 prefill_per_tick: int = 1, q_chunk: int = 256):
        if n_slots < 1:
            raise ValueError("need at least one decode slot")
        self.cfg = cfg
        self.executor = executor
        self.ledger = executor.ledger
        self.max_len = max_len
        self.n_slots = n_slots
        self.prefill_per_tick = prefill_per_tick
        self.kv = kv if kv is not None else PagedKVCache()  # len()==0 is falsy
        self.ledger.attach_pool("kv_pages", self.kv.pool)
        self.regions = make_serve_regions(cfg, mesh, params,
                                          ledger=self.ledger, q_chunk=q_chunk)

        raw_decode = self.regions.decode_step.impl_fn("ref")

        @region("DECODE_SLOTS", ledger=self.ledger,
                size_fn=self.regions.decode_step.size_fn)
        def decode_slots(params, tok, cache, pos, active):
            # the DECODE_STEP body per slot: batch-1 decode, per-slot pos —
            # identical math to the solo path, batched over the slot axis
            new_tok, new_cache = jax.vmap(
                raw_decode, in_axes=(None, 0, 0, 0))(params, tok, cache, pos)
            new_tok = jnp.where(active[:, None], new_tok, tok)
            return new_tok, new_cache

        @region("SLOT_ADMIT", ledger=self.ledger, offloaded=False)
        def slot_admit(slot_cache, req_cache, slot_idx):
            def scatter(sc, rc):
                starts = (slot_idx,) + (0,) * rc.ndim
                return jax.lax.dynamic_update_slice(sc, rc[None], starts)
            return jax.tree.map(scatter, slot_cache, req_cache)

        self._decode_slots = decode_slots
        self._slot_admit = slot_admit

        # slot state: stacked batch-1 caches [n_slots, 1, ...] plus
        # host-side token/position/active vectors (program inputs per tick)
        base = T.init_cache(cfg, 1, max_len)
        self.slot_cache = jax.tree.map(
            lambda x: jnp.stack([x] * n_slots), base)
        self._tok = np.zeros(n_slots, np.int32)
        self._pos = np.zeros(n_slots, np.int32)
        self._active = np.zeros(n_slots, bool)
        self.slot_req: List[Optional[Request]] = [None] * n_slots

        # ONE captured tick program; pos and the active mask are program
        # INPUTS (live per replay), unlike the static decode program's
        # frozen positions.  Capture runs the tick eagerly once — that is
        # the engine's compile warm-up; all-empty slots are numerically
        # inert (finite-NEG_INF masking) and their rows are overwritten
        # wholesale at admission.
        self.tick_prog = capture(
            self._tick_fn, jnp.asarray(self._tok[:, None]), self.slot_cache,
            jnp.asarray(self._pos), jnp.asarray(self._active),
            name="engine_tick")

        self._prefill_progs: Dict[int, Any] = {}
        self.queued: Deque[Request] = collections.deque()
        self.waiting: Deque[Request] = collections.deque()
        self.requests: Dict[int, Request] = {}
        self.ticks = 0

    def _tick_fn(self, run, tok, cache, pos, active):
        tok, cache = run(self._decode_slots, self.regions.params, tok, cache,
                         pos, active)
        cache = run(self.regions.kv_append, cache)
        return tok, cache

    # -- request intake ------------------------------------------------
    def submit(self, req: Request) -> Request:
        if req.req_id in self.requests:
            raise ValueError(f"duplicate req_id {req.req_id}")
        if req.prompt_len + req.gen > self.max_len:
            raise ValueError(
                f"request {req.req_id}: prompt {req.prompt_len} + gen "
                f"{req.gen} exceeds engine max_len {self.max_len}")
        req.submit_time = time.perf_counter()
        self.requests[req.req_id] = req
        self.queued.append(req)
        self.ledger.serve_record("submitted")
        return req

    # -- state machine -------------------------------------------------
    def _set_state(self, req: Request, state: str) -> None:
        if state not in _TRANSITIONS[req.state]:
            raise RuntimeError(f"request {req.req_id}: illegal transition "
                               f"{req.state} -> {state}")
        req.state = state
        req.history.append(state)

    # -- prefill (length-bucketed) --------------------------------------
    def _prefill_program(self, prompt_len: int, example_batch, example_cache):
        prog = self._prefill_progs.get(prompt_len)
        if prog is None:
            prog = capture_prefill_program(
                self.regions, example_batch, example_cache,
                name=f"prefill_L{prompt_len}")
            self._prefill_progs[prompt_len] = prog
        return prog

    def _prefill(self, req: Request) -> None:
        batch = batch_for_prompt(self.cfg, req.prompt)
        cache0 = T.init_cache(self.cfg, 1, self.max_len)
        prog = self._prefill_program(req.prompt_len, batch, cache0)
        tok, cache = prog.replay(self.executor, batch, cache0)
        req.tokens = [int(np.asarray(tok)[0])]
        req.token_times = [time.perf_counter()]
        req.pos = req.prompt_len
        self.ledger.serve_record("prefills")
        if req.gen <= 1:                    # finished at prefill: no slot
            self._set_state(req, DONE)
            self.ledger.serve_record("retired")
            return
        evicted = self.kv.commit(req.req_id, cache, true_len=req.prompt_len)
        self._set_state(req, PREFILL)
        self.waiting.append(req)
        for rid in evicted:
            self._evict(self.requests[rid])

    def _evict(self, req: Request) -> None:
        """Total-budget eviction: the parked prefill is lost — drop its
        tokens and re-queue for a fresh prefill (pages already freed)."""
        self.waiting.remove(req)
        req.evictions += 1
        req.tokens = []
        req.token_times = []
        self._set_state(req, EVICTED)
        self._set_state(req, QUEUED)
        self.queued.appendleft(req)         # it arrived first: keep order
        self.ledger.serve_record("evicted")

    # -- admission ------------------------------------------------------
    def _admit(self, req: Request, slot: int) -> None:
        cache = self.kv.gather(req.req_id)
        self.slot_cache = self.executor.run(
            self._slot_admit, self.slot_cache, cache, jnp.int32(slot))
        self._tok[slot] = req.tokens[-1]
        self._pos[slot] = req.pos
        self._active[slot] = True
        self.slot_req[slot] = req
        req.slot = slot
        self._set_state(req, DECODE)
        self.ledger.serve_record("admitted")

    # -- decode tick ----------------------------------------------------
    def _decode_tick(self) -> None:
        n_active = int(self._active.sum())
        tok, cache = self.tick_prog.replay(
            self.executor, jnp.asarray(self._tok[:, None]), self.slot_cache,
            jnp.asarray(self._pos), jnp.asarray(self._active))
        self.slot_cache = cache
        tok_np = np.asarray(tok)
        now = time.perf_counter()
        for s in np.nonzero(self._active)[0]:
            req = self.slot_req[s]
            t = int(tok_np[s, 0])
            req.tokens.append(t)
            req.token_times.append(now)
            req.pos += 1
            self._tok[s] = t
            self._pos[s] = req.pos
            if len(req.tokens) >= req.gen:
                self._retire(req, int(s))
        self.ticks += 1
        self.ledger.serve_record("ticks")
        self.ledger.serve_record("decode_tokens", n_active)
        self.ledger.serve_record("active_slot_ticks", n_active)

    def _retire(self, req: Request, slot: int) -> None:
        self._active[slot] = False
        self.slot_req[slot] = None
        req.slot = None
        self._set_state(req, DONE)
        self.ledger.serve_record("retired")

    # -- the engine step ------------------------------------------------
    def step(self) -> bool:
        """One engine tick: prefill-interleave, admit, decode.  Returns
        whether any work was done (False = fully drained)."""
        did = False
        # prefill interleaving, throttled: parking more than a full slot
        # complement ahead just grows the paged store (and, under a total
        # budget, thrashes it)
        for _ in range(self.prefill_per_tick):
            if not self.queued or len(self.waiting) >= self.n_slots:
                break
            self._prefill(self.queued.popleft())
            did = True
        while self.waiting and not self._active.all():
            slot = int(np.nonzero(~self._active)[0][0])
            self._admit(self.waiting.popleft(), slot)
            did = True
        if self._active.any():
            self._decode_tick()
            did = True
        self._push_gauges()
        return did

    def drain(self, max_ticks: int = 100_000) -> None:
        """Step until every submitted request is DONE."""
        for _ in range(max_ticks):
            if not self.step():
                return
        raise RuntimeError(f"engine did not drain in {max_ticks} ticks")

    def _push_gauges(self) -> None:
        led = self.ledger
        counters = led.serve_counters
        if counters.get("ticks"):
            # peak running occupancy: active slot-ticks per slot capacity
            led.serve_gauge("slot_occupancy",
                            counters.get("active_slot_ticks", 0)
                            / (counters["ticks"] * self.n_slots))
        st = self.kv.stats
        led.serve_gauge("kv_device_page_high_water_bytes",
                        st.device_high_water_bytes)
        led.serve_gauge("kv_total_page_high_water_bytes",
                        st.total_high_water_bytes)
        led.serve_gauge("kv_slot_cache_bytes", sum(
            int(x.nbytes) for x in jax.tree.leaves(self.slot_cache)))
        budget = getattr(self.kv, "budget", None)
        if budget is not None:
            # oversubscription gauges: how hard the logical device budget
            # was pressed and how much the LRU spill path had to shed
            led.serve_gauge("kv_budget_limit_bytes",
                            budget.limit_bytes or 0)
            led.serve_gauge("kv_budget_high_water_bytes",
                            budget.stats.high_water_bytes)
            led.serve_gauge("kv_budget_pressure_events",
                            budget.stats.pressure_events)
