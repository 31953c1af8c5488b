"""Batched serving driver — prefill + decode on the region-program spine.

Serving is where the unified-memory policy earns its keep (paper C1/C4).
The request path is three directive-sized regions — ``PREFILL``,
``DECODE_STEP``, ``KV_APPEND`` — captured as two RegionPrograms (one
prefill call; one greedy decode loop, one ``DECODE_STEP`` + ``KV_APPEND``
pair per generated token) and replayed through an ``Executor`` under any
``--policy``; ``--replay-batch N`` pushes N independent request groups
through the decode program as ONE vmapped composite
(``RegionProgram.replay_batch``, the heavy-traffic path).

``--offload-kv`` is *just a policy choice*: :func:`offload_kv_cache`
builds a role-keyed :class:`KVCachePlacer` — only the actual ``k``/``v``
cache pages (megabytes at serving scale) above ``min_bytes`` move to host
DRAM; slot/position bookkeeping is decode-hot and stays deviceside no
matter how large.  The decode math never changes, only the placement axis.

The pre-capture jit path (:func:`build_server` + :func:`decode_stream`)
remains as the streaming reference: the decode loop syncs once per
``--sync-every`` tokens (0 = end of stream) instead of per token — a
per-token ``block_until_ready`` serializes the stream, and ``fig_serve``
(benchmarks/run.py) records the reclaimed latency.  Under
``UnifiedPolicy`` the captured-program tokens are asserted bit-identical
to this jit path on every run.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --batch 4 --prompt-len 32 --gen 32 --policy unified --report
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.reduced import reduced as make_reduced
from repro.configs.registry import get_config
from repro.core.ledger import Ledger
from repro.core.program import AsyncExecutor, capture
from repro.core.regions import (Executor, Placer, UnifiedPolicy,
                                default_size, region)
from repro.core.umem import (MemSpace, device_operands,
                             preferred_host_space, space_of, tree_place)
from repro.launch.compilation import configure_compilation
from repro.launch import sharding as SH
from repro.launch.mesh import make_smoke_mesh
from repro.launch.policy import PLACER_MIN_BYTES, POLICY_CHOICES, lm_policy
from repro.models import transformer as T
from repro.train import step as S


# placement is keyed on tensor ROLE, not just size: only the actual k/v
# pages (batch*heads*len*head_dim — megabytes at serving scale) go to host
# DRAM; slot/position bookkeeping is decode-hot and stays deviceside no
# matter how large. min_bytes additionally keeps smoke-scale k/v pages,
# where the crossing costs more than it saves, where they are.
KV_PLACE_KEYS = ("k", "v")
KV_PLACE_MIN_BYTES = 32768


def place_kv_leaves(tree, space: MemSpace, min_bytes=KV_PLACE_MIN_BYTES):
    """Role-keyed placement: move only ``k``/``v``-named leaves above
    ``min_bytes`` to ``space``; every other leaf stays put."""
    def per_leaf(path, x):
        keys = {getattr(p, "key", None) for p in path}
        if keys & set(KV_PLACE_KEYS):
            return tree_place(x, space, min_bytes=min_bytes)
        return x
    return jax.tree_util.tree_map_with_path(per_leaf, tree)


@dataclasses.dataclass
class KVCachePlacer(Placer):
    """KV-cache offload as a *placement axis* (:class:`Placer` subclass).

    On top of the base hint behavior, every region's arguments and results
    get the role-keyed treatment of :func:`place_kv_leaves`: ``k``/``v``
    cache pages above ``kv_min_bytes`` are re-homed to ``kv_space`` each
    time they cross a region boundary — the ``KV_APPEND`` commit point in
    the decode program re-places the token's freshly appended pages.  With
    ``kv_space=None`` this is exactly the base :class:`Placer`.
    """
    kv_space: Optional[MemSpace] = None
    kv_min_bytes: int = KV_PLACE_MIN_BYTES

    def place_args(self, target_region, args, kwargs):
        args, kwargs = super().place_args(target_region, args, kwargs)
        if self.kv_space is None:
            return args, kwargs
        return place_kv_leaves((args, kwargs), self.kv_space,
                               self.kv_min_bytes)

    def place_result(self, target_region, out):
        out = super().place_result(target_region, out)
        if self.kv_space is None:
            return out
        return place_kv_leaves(out, self.kv_space, self.kv_min_bytes)


def kv_spaces(tree) -> set:
    """Memory kinds the ``k``/``v``-keyed leaves of a cache tree live in."""
    out = set()

    def per_leaf(path, x):
        if {getattr(p, "key", None) for p in path} & set(KV_PLACE_KEYS):
            out.add(space_of(x))
        return x
    jax.tree_util.tree_map_with_path(per_leaf, tree)
    return out


def offload_kv_cache(space: Optional[MemSpace] = None,
                     min_bytes: int = KV_PLACE_MIN_BYTES) -> KVCachePlacer:
    """The ``--offload-kv`` Placer: role-keyed KV offload to host DRAM
    (``preferred_host_space()`` unless ``space`` names one explicitly)."""
    return KVCachePlacer(min_bytes=PLACER_MIN_BYTES,
                         kv_space=space or preferred_host_space(),
                         kv_min_bytes=min_bytes)


# ---------------------------------------------------------------------------
# Serving regions + captured programs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeRegions:
    """The request path as directive-sized regions; the model regions take
    ``params`` as their first argument."""
    params: Any
    prefill: Any        # (params, batch, cache)    -> (tok, cache)
    decode_step: Any    # (params, tok, cache, pos) -> (tok, cache)
    kv_append: Any      # (cache,)                  -> cache


def _state_size(args, kwargs) -> int:
    """Problem size of a model region: its request state, not its weights
    (the routing clause must see the batch, not the embedding table)."""
    return default_size(args[1:], kwargs)


def make_serve_regions(cfg, mesh, params, *, ledger: Optional[Ledger] = None,
                       q_chunk: int = 256) -> ServeRegions:
    """``PREFILL`` / ``DECODE_STEP`` / ``KV_APPEND`` on one ledger.

    ``params`` are an argument of every model region, never closed over:
    jit embeds a closed-over array in the program as a literal constant,
    gigabytes per program at published widths.  Captured programs pass
    them as a constant input, which is what ``replay_batch`` wants: under
    ``vmap`` they broadcast across the N stacked requests while tokens and
    caches batch.  ``KV_APPEND`` is the cache *commit* directive: the
    model's fused insert runs inside ``DECODE_STEP`` (attention appends as
    it attends), and this math-identity region is where the policy's
    placement axis re-homes the appended pages (role-keyed
    ``--offload-kv``) and the ledger accounts the per-token cache commit.
    ``offloaded=False``: commitment is bookkeeping, not a staged offload —
    no policy stages the whole cache twice per token.
    """
    rules = SH.ShardingRules("serve")
    shd = SH.make_sharder(mesh, rules)
    raw_prefill = S.make_prefill_step(
        cfg, lambda: T.Ctx(mode="prefill", shd=shd, q_chunk=q_chunk,
                           remat=False))
    raw_decode = S.make_decode_step(
        cfg, lambda: T.Ctx(mode="decode", shd=shd, remat=False))

    @region("PREFILL", ledger=ledger, size_fn=_state_size)
    def prefill_region(params, batch, cache):
        logits, cache = raw_prefill(params, batch, cache)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    @region("DECODE_STEP", ledger=ledger, size_fn=_state_size)
    def decode_region(params, tok, cache, pos):
        logits, cache = raw_decode(params, tok, cache, pos)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    # donate_args: the cache fed to KV_APPEND is always the PREVIOUS
    # region's output (never a program input), and this commit is its last
    # consumer — XLA aliases the buffers through, so the pass-through costs
    # O(1), not an O(cache-bytes) copy per token
    @region("KV_APPEND", ledger=ledger, offloaded=False, donate_args=(0,))
    def kv_append(cache):
        return cache

    return ServeRegions(params=params, prefill=prefill_region,
                        decode_step=decode_region, kv_append=kv_append)


def capture_prefill_program(regions: ServeRegions, example_batch,
                            example_cache, name: str = "prefill_program"):
    """Prefill as a RegionProgram: one ``PREFILL`` call, then the
    ``KV_APPEND`` commit of the prompt's cache pages."""
    def prefill_fn(run, batch, cache):
        tok, cache = run(regions.prefill, regions.params, batch, cache)
        cache = run(regions.kv_append, cache)
        return tok, cache

    return capture(prefill_fn, example_batch, example_cache, name=name)


def capture_decode_program(regions: ServeRegions, prompt_len: int, gen: int,
                           example_tok, example_cache,
                           name: str = "decode_program"):
    """The greedy decode loop as one RegionProgram.

    Each generated token is one ``DECODE_STEP`` (decode + argmax) whose KV
    cache flows into a ``KV_APPEND`` commit and on to the next token, so
    the captured trace carries the full request dataflow; positions are
    frozen constants (CUDA-graph style).
    """
    def gen_loop(run, tok, cache):
        toks = [tok]
        for i in range(gen - 1):
            tok, cache = run(regions.decode_step, regions.params, tok,
                             cache, jnp.int32(prompt_len + i))
            cache = run(regions.kv_append, cache)
            toks.append(tok)
        return tuple(toks)      # tuple of refs (stacking outside a region
        #                         would freeze the result as a constant)

    return capture(gen_loop, example_tok, example_cache, name=name)


# ---------------------------------------------------------------------------
# Pre-capture jit path (streaming reference)
# ---------------------------------------------------------------------------

def build_server(cfg, mesh, batch: int, max_len: int, q_chunk=256,
                 offload_kv=False):
    rules = SH.ShardingRules("serve")
    shd = SH.make_sharder(mesh, rules)
    prefill = jax.jit(device_operands(S.make_prefill_step(
        cfg, lambda: T.Ctx(mode="prefill", shd=shd, q_chunk=q_chunk,
                           remat=False))))
    decode = jax.jit(device_operands(S.make_decode_step(
        cfg, lambda: T.Ctx(mode="decode", shd=shd, remat=False))),
        donate_argnums=(2,))

    # KV placement is a MemSpace hint, not a hand-rolled sharding: pages big
    # enough to matter go to host DRAM, small tensors stay put (paper C1/C4)
    kv_space = preferred_host_space() if offload_kv else None

    def make_cache():
        cache = T.init_cache(cfg, batch, max_len)
        if kv_space is not None:
            cache = place_kv_leaves(cache, kv_space)
        return cache

    return prefill, decode, make_cache


def decode_stream(decode, params, tok, cache, prompt_len: int, gen: int,
                  sync_every: int = 0):
    """Greedy decode on the raw jit path with interval syncing.

    ``sync_every <= 0`` means *never* sync mid-stream: the whole stream
    dispatches asynchronously and blocks exactly once on the final token —
    the maximally-overlapped default.  (Before this was pinned down, a
    negative value fell through the modulo and silently behaved like the
    per-token sync.)  ``sync_every = 1`` is that retired per-token
    ``jax.block_until_ready`` — dispatch of token *i+1* cannot start until
    *i* has fully materialized; larger intervals reclaim the latency one
    report interval at a time (measured by ``fig_serve``)."""
    toks = [tok]
    for i in range(gen - 1):
        logits, cache = decode(params, tok, cache, jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks.append(tok)
        if sync_every > 0 and (i + 1) % sync_every == 0:
            jax.block_until_ready(tok)
    jax.block_until_ready(toks[-1])
    return toks, cache


# ---------------------------------------------------------------------------
# Heavy traffic: replay_batch over N request groups
# ---------------------------------------------------------------------------

def replay_batch_demo(cfg, ex, decode_prog, prefill, make_cache,
                      params, args, n_requests: int, apu_mesh_size: int = 0):
    """The "heavy traffic" path: push N independent request groups through
    the captured decode program as ONE vmapped composite
    (``RegionProgram.replay_batch``).

    ``apu_mesh_size`` > 0 additionally scatters the stacked request groups
    across a 1-D mesh of simulated APUs (``repro.core.shard_program``):
    each APU decodes its slice of the requests through the same compiled
    composite, with per-device ledgers aggregated in the printed report.
    Needs ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` exported
    before launch (see docs/SCALING.md)."""
    key0 = jax.random.PRNGKey(args.seed)
    toks, caches = [], []
    for r in range(n_requests):
        key = jax.random.fold_in(key0, r)
        prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                     cfg.vocab, jnp.int32)
        batch = _prefill_inputs(cfg, args, prompts)
        logits, cache = prefill(params, batch, make_cache())
        toks.append(jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32))
        caches.append(cache)

    stacked_tok = jnp.stack(toks)
    stacked_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    sharded = None
    if apu_mesh_size:
        from repro.core.shard_program import shard_program
        from repro.launch.mesh import make_apu_mesh
        if n_requests % apu_mesh_size:
            raise SystemExit(f"--replay-batch {n_requests} does not divide "
                             f"over --mesh {apu_mesh_size} APUs")
        sharded = shard_program(decode_prog, make_apu_mesh(apu_mesh_size),
                                UnifiedPolicy(), shard_dim=0)
    t0 = time.time()
    if sharded is not None:
        out = sharded.replay_batch(stacked_tok, stacked_cache)
    else:
        out = decode_prog.replay_batch(stacked_tok, stacked_cache,
                                       executor=ex)
    dt = time.time() - t0
    seqs = np.asarray(jnp.stack(out, axis=-1))        # (N, B, gen)
    assert np.isfinite(seqs).all()
    # request 0 replayed alone through the same program (vmap-free):
    # agreement can drop below 1.0 only via argmax ties under batched matmul
    solo = np.asarray(jnp.stack(decode_prog.replay(ex, toks[0], caches[0]),
                                axis=-1))
    agree = float((seqs[0] == solo).mean())
    total = n_requests * args.batch * args.gen
    shard_note = ""
    if sharded is not None:
        rep = sharded.coverage_report()
        # NB: no exchange figure here — the batched path scatters whole
        # independent requests, so there is no halo traffic to model
        shard_note = (f"; sharded over {rep['devices']} APUs "
                      f"({n_requests // rep['devices']} request groups "
                      f"each)")
    print(f"[serve] replay_batch: {n_requests} request groups x "
          f"{args.batch}x{args.gen} tokens = {total} tokens in "
          f"{dt*1e3:.1f} ms ({total/max(dt,1e-9):.0f} tok/s); "
          f"solo-replay agreement {agree:.3f}{shard_note}")
    return seqs


def _prefill_inputs(cfg, args, prompts):
    batch = {"tokens": prompts}
    if cfg.mrope_sections is not None:
        pos = jnp.arange(args.prompt_len, dtype=jnp.int32)[None, :, None]
        batch["positions3"] = jnp.broadcast_to(
            pos, (args.batch, args.prompt_len, 3))
    if cfg.n_enc_layers:
        batch["enc_embeds"] = jnp.zeros(
            (args.batch, cfg.enc_len, cfg.d_model), cfg.compute_dtype)
    return batch


def _verify_programs(ex, *progs):
    """``--verify``: statically lint freshly captured programs under the
    serving executor's policy (repro.analysis) before any replay; findings
    print, error severity aborts startup (docs/ANALYSIS.md)."""
    for prog in progs:
        rep = prog.verify(ex.policy, ledger=ex.ledger)
        print(f"[verify] {rep.summary()}")
        for d in rep.findings:
            print(f"    {d}")
        if rep.errors:
            raise SystemExit(f"[verify] {prog.name!r} has error-severity "
                             "findings; refusing to serve")


def _engine_demo(cfg, mesh, params, ex, args, max_len):
    """Continuous-batching engine under the launcher flags: seeded Poisson
    traffic with ragged prompt/gen lengths through
    :class:`repro.serve.ServeEngine`, each token checked against the solo
    jit path's logits (``assert_logit_parity``, docs/SERVING.md).  Returns
    the traffic metrics, each request's tokens (``outputs``), the memory
    kinds of the slot cache's k/v pages (``kv_spaces``) and the parity
    check's counts (``parity``)."""
    # lazy import: repro.serve runs ON this module's regions and programs
    from repro.serve import (PagedKVCache, ServeEngine, make_traffic,
                             run_traffic, solo_reference)
    from repro.serve.traffic import LOGIT_TOL_ULPS, assert_logit_parity

    budget = None
    if args.kv_oversub_ratio > 0:
        # oversubscription mode: derive the logical device budget from the
        # measured footprint of one parked full-length entry x slots, so
        # --kv-oversub-ratio 2 means "the KV working set is 2x device
        # capacity" regardless of model size (see docs/EXPERIMENTS.md)
        from repro.core.oversub import MemoryBudget
        probe = PagedKVCache(page_tokens=args.page_tokens)
        probe.commit(0, T.init_cache(cfg, 1, max_len), true_len=max_len)
        footprint = probe.total_bytes * args.slots
        probe.free(0)
        budget = MemoryBudget.for_ratio(footprint, args.kv_oversub_ratio,
                                        name="kv")
    kv = PagedKVCache(page_tokens=args.page_tokens,
                      device_budget_bytes=args.kv_device_budget or None,
                      total_budget_bytes=args.kv_total_budget or None,
                      budget=budget)
    engine = ServeEngine(cfg, mesh, params, ex, max_len=max_len,
                         n_slots=args.slots, kv=kv)
    if args.verify:
        _verify_programs(ex, engine.tick_prog)
    lens = sorted({max(2, args.prompt_len // 2), args.prompt_len})
    gens = sorted({1, max(2, args.gen // 2), args.gen})
    reqs = make_traffic(args.seed, args.requests, cfg.vocab,
                        arrival_rate=args.rate, prompt_lens=lens,
                        gen_lens=gens)
    metrics = run_traffic(engine, reqs)
    oracle, solo_wall = solo_reference(cfg, mesh, params, reqs, max_len,
                                       offload_kv=args.offload_kv)
    # the acceptance invariant: every engine token is the solo path's top
    # logit, teacher-forced, up to a stated bf16 tolerance
    par = assert_logit_parity(cfg, mesh, params, reqs, oracle, max_len,
                              offload_kv=args.offload_kv)
    solo_tps = metrics["tokens"] / max(solo_wall, 1e-9)
    st = kv.stats
    spill_note = (f"; {st.pages_spilled} pages spilled to host"
                  f" ({st.pages_fetched} fetched back)"
                  if st.pages_spilled else "")
    evict_note = f"; {st.evictions} evictions" if st.evictions else ""
    if budget is not None:
        evict_note += (f"; oversub x{args.kv_oversub_ratio:g} budget "
                       f"{budget.limit_bytes} B (high-water "
                       f"{budget.stats.high_water_bytes} B, "
                       f"{budget.stats.pressure_events} pressure events)")
    print(f"[serve] engine {args.arch}"
          f"{' (reduced)' if args.reduced else ''} [{ex.mode}]: "
          f"{metrics['requests']} requests / {metrics['tokens']} tokens in "
          f"{metrics['wall_s']*1e3:.1f} ms — {metrics['tokens_per_s']:.0f} "
          f"tok/s engine vs {solo_tps:.0f} tok/s sequential solo jit; "
          f"p50 {metrics.get('p50_token_ms', 0.0):.2f} / p99 "
          f"{metrics.get('p99_token_ms', 0.0):.2f} ms/token; KV page "
          f"high-water {st.device_high_water_bytes} B device"
          f"{spill_note}{evict_note}; parity OK vs solo jit: "
          f"{par['tokens']} tokens within {LOGIT_TOL_ULPS} bf16 spacings of "
          f"the teacher-forced solo top logit (max {par['gap_max']:.1f}), "
          f"{par['diverged']}/{len(reqs)} streams left the free-running "
          f"solo decode; slot KV in "
          f"{'/'.join(sorted(kv_spaces(engine.slot_cache)))}")
    if args.report:
        print(json.dumps(ex.report(), indent=1, default=str))
    return {**metrics, "outputs": {r.req_id: list(r.tokens) for r in reqs},
            "kv_spaces": sorted(kv_spaces(engine.slot_cache)),
            "parity": par}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--offload-kv", action="store_true",
                    help="role-keyed KV offload to host DRAM — a Placer "
                         "swapped into the policy, nothing else changes")
    ap.add_argument("--policy", default="unified", choices=POLICY_CHOICES,
                    help="ExecutionPolicy the serving regions run under "
                         "(adaptive threads cfg.memory.target_cutoff)")
    ap.add_argument("--verify", action="store_true",
                    help="statically lint every captured program "
                         "(PREFILL/DECODE_STEP/KV_APPEND, or the engine "
                         "tick) under the serving policy at startup; "
                         "error-severity findings abort (repro.analysis, "
                         "docs/ANALYSIS.md)")
    ap.add_argument("--report", action="store_true",
                    help="print the run's coverage_report() as JSON")
    ap.add_argument("--sync-every", type=int, default=0, metavar="K",
                    help="jit streaming path: block_until_ready once per K "
                         "tokens; K <= 0 = never sync mid-stream, one "
                         "final sync at end of stream; 1 = the retired "
                         "per-token sync")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine instead of the static "
                         "batch: Poisson traffic through slot-scheduled "
                         "decode over a paged KV cache, every token "
                         "checked against solo jit logits (docs/SERVING.md); "
                         "composes with any --policy and --offload-kv")
    ap.add_argument("--slots", type=int, default=4, metavar="N",
                    help="engine decode slots (the vmapped tick width)")
    ap.add_argument("--requests", type=int, default=8, metavar="N",
                    help="engine traffic size (seeded by --seed)")
    ap.add_argument("--rate", type=float, default=1.0, metavar="R",
                    help="engine mean arrivals per tick (Poisson)")
    ap.add_argument("--page-tokens", type=int, default=8, metavar="T",
                    help="engine KV page size along the token axis")
    ap.add_argument("--kv-device-budget", type=int, default=0, metavar="B",
                    help="engine paged-KV device budget in bytes; exceeding "
                         "it spills LRU entries to host DRAM (0 = "
                         "unlimited)")
    ap.add_argument("--kv-total-budget", type=int, default=0, metavar="B",
                    help="engine paged-KV total budget in bytes; exceeding "
                         "it evicts+requeues LRU requests (0 = unlimited)")
    ap.add_argument("--kv-oversub-ratio", type=float, default=0.0,
                    metavar="R",
                    help="engine KV oversubscription ratio: set the logical "
                         "device budget (repro.core.oversub.MemoryBudget) "
                         "to 1/R of the measured slots-x-full-length KV "
                         "footprint, so R=2 runs a working set twice "
                         "device capacity — LRU spill keeps it inside "
                         "(0 = off)")
    ap.add_argument("--replay-batch", type=int, default=0, metavar="N",
                    help="also push N stacked request groups through the "
                         "captured decode program "
                         "(RegionProgram.replay_batch heavy-traffic path)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="scatter the --replay-batch request groups over a "
                         "mesh of N simulated APUs (shard_program); export "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N before launch, see docs/SCALING.md")
    args = ap.parse_args(argv)
    configure_compilation()
    if args.mesh and not args.replay_batch:
        raise SystemExit("--mesh requires --replay-batch N (it shards the "
                         "batched decode program)")
    if args.engine and (args.replay_batch or args.mesh):
        raise SystemExit("--engine replaces the static batch paths; it "
                         "does not compose with --replay-batch/--mesh")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    # with --mesh N the model mesh spans the same N simulated APUs as the
    # shard_program mesh — one jit cannot mix two device assignments
    mesh = make_smoke_mesh((args.mesh, 1)) if args.mesh else make_smoke_mesh()
    max_len = args.prompt_len + args.gen
    placer = offload_kv_cache() if args.offload_kv else None
    if args.policy == "auto":
        # tuned warm-start: the profile's serve_decode winner for this
        # request shape (lazy import — repro.tune pulls this driver back
        # in for its workload harness)
        from repro.launch.policy import auto_policy
        from repro.tune.space import serve_size
        pol = auto_policy("serve_decode",
                          serve_size(args.batch, max_len, cfg.d_model),
                          cfg.memory, placer=placer)
        entry = getattr(pol, "tuned_entry", None)
        if entry is not None and entry.candidate.staging == "async":
            ex = AsyncExecutor(pol, Ledger("serve"))
        else:
            ex = Executor(pol, Ledger("serve"))
    else:
        ex = Executor(lm_policy(args.policy, cfg.memory, placer=placer),
                      Ledger("serve"))
    key = jax.random.PRNGKey(args.seed)
    params = T.init(key, cfg)
    if args.engine:
        return _engine_demo(cfg, mesh, params, ex, args, max_len)
    regions = make_serve_regions(cfg, mesh, params, ledger=ex.ledger)

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab, jnp.int32)
    batch = _prefill_inputs(cfg, args, prompts)

    # -- captured-program path (the accounted serving spine) -------------
    prefill_prog = capture_prefill_program(regions, batch,
                                           T.init_cache(cfg, args.batch,
                                                        max_len))
    if args.verify:
        _verify_programs(ex, prefill_prog)
    t0 = time.time()
    tok, cache = prefill_prog.replay(ex, batch,
                                     T.init_cache(cfg, args.batch, max_len))
    t_prefill = time.time() - t0
    decode_prog = capture_decode_program(regions, args.prompt_len, args.gen,
                                         tok, cache)
    if args.verify:
        _verify_programs(ex, decode_prog)
    t1 = time.time()
    toks = decode_prog.replay(ex, tok, cache)
    t_decode = time.time() - t1
    seq = np.asarray(jnp.stack(toks, axis=1))
    assert np.isfinite(seq).all()

    # -- pre-capture jit streaming path (interval sync) -------------------
    # built only when needed: under UnifiedPolicy it doubles as the parity
    # oracle (capture changes the schedule, never the tokens); other
    # policies change placement/staging, not math — re-running the jit
    # stream there would double the run for numbers the report carries
    stream_note = ""
    prefill = make_cache = None
    if args.policy == "unified" or args.replay_batch:
        prefill, decode, make_cache = build_server(
            cfg, mesh, args.batch, max_len, offload_kv=args.offload_kv)
    if args.policy == "unified":
        logits, cache_j = prefill(params, batch, make_cache())
        tok_j = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        # warm the decode executable on a throwaway prefill output (a
        # fresh make_cache() has different sharding than the prefill
        # result and would compile a second executable) so the stream
        # timing measures the stream, not the compile
        _, cache_w = prefill(params, batch, make_cache())
        jax.block_until_ready(decode(params, tok_j, cache_w,
                                     jnp.int32(args.prompt_len)))
        t2 = time.time()
        toks_j, _ = decode_stream(decode, params, tok_j, cache_j,
                                  args.prompt_len, args.gen,
                                  sync_every=args.sync_every)
        t_stream = time.time() - t2
        seq_j = np.asarray(jnp.stack(toks_j, axis=1))
        # the acceptance invariant: program tokens == jit-path tokens
        np.testing.assert_array_equal(seq, seq_j)
        total_new = args.batch * args.gen
        stream_note = f", {total_new/max(t_stream,1e-9):.0f} tok/s stream"

    total_new = args.batch * args.gen
    print(f"[serve] {args.arch}{' (reduced)' if args.reduced else ''} "
          f"[{ex.mode}]: prefill {args.batch}x{args.prompt_len} in "
          f"{t_prefill*1e3:.1f} ms; decode {total_new} tokens in "
          f"{t_decode*1e3:.1f} ms ({total_new/max(t_decode,1e-9):.0f} tok/s "
          f"program{stream_note})"
          + (f" [KV in {preferred_host_space().kind}]"
             if args.offload_kv and preferred_host_space() else ""))
    if args.replay_batch:
        replay_batch_demo(cfg, ex, decode_prog, prefill, make_cache,
                          params, args, args.replay_batch,
                          apu_mesh_size=args.mesh)
    if args.report:
        print(json.dumps(ex.report(), indent=1, default=str))
    return seq


if __name__ == "__main__":
    main()
