"""Continuous-batching engine: paged KV store, slot scheduler, parity.

The contract under test (docs/SERVING.md): the engine may page, spill,
evict, re-prefill, and batch requests across slots however its budgets
dictate — but every request's token sequence stays bit-identical to a
solo jit decode of the same prompt, under every policy.  Alongside: the
pool byte accounting (`bytes_in_use` / `high_water_bytes`), the ledger's
``serve`` / ``pools`` report sections, and the pinned-down
``decode_stream`` sync semantics (``sync_every <= 0`` = one final sync).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.reduced import reduced as make_reduced
from repro.configs.registry import get_config
from repro.core.ledger import Ledger
from repro.core.pool import DeviceBufferPool, HostStagingPool
from repro.core.regions import Executor, UnifiedPolicy
from repro.launch import serve as SV
from repro.launch.mesh import make_smoke_mesh
from repro.launch.policy import lm_policy
from repro.models import transformer as T
from repro.serve import (PagedKVCache, Request, ServeEngine, make_traffic,
                         run_traffic, solo_reference)
from repro.serve.scheduler import DECODE, DONE, QUEUED
from repro.serve.traffic import assert_parity

MAX_LEN = 16


@pytest.fixture(scope="module")
def setup(traffic_seed):
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    mesh = make_smoke_mesh()
    params = T.init(jax.random.PRNGKey(0), cfg)
    reqs = _traffic(cfg, traffic_seed)
    oracle, _ = solo_reference(cfg, mesh, params, reqs, MAX_LEN)
    return {"cfg": cfg, "mesh": mesh, "params": params, "oracle": oracle,
            "seed": traffic_seed}


def _traffic(cfg, seed):
    # the seed comes from the session `traffic_seed` fixture (conftest.py)
    # so every engine run and its parity oracle share one request stream
    return make_traffic(seed=seed, n_requests=4, vocab=cfg.vocab,
                        arrival_rate=2.0, prompt_lens=(6, 10),
                        gen_lens=(1, 5))


def _engine(s, policy=None, ledger_name="engine", **kv_kwargs):
    ex = Executor(policy or UnifiedPolicy(), Ledger(ledger_name))
    kv = PagedKVCache(page_tokens=4, **kv_kwargs)
    eng = ServeEngine(s["cfg"], s["mesh"], s["params"], ex,
                      max_len=MAX_LEN, n_slots=2, kv=kv)
    return eng, ex, kv


def _filled_cache(cfg, max_len=MAX_LEN, true_len=10):
    """A batch-1 cache with random values in [0, true_len) and the exact
    init_cache tail beyond — the shape a prefill leaves behind."""
    cache = T.init_cache(cfg, 1, max_len)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for i, (path, leaf) in enumerate(flat):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(leaf)
            continue
        v = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(1), i),
                              leaf.shape, leaf.dtype)
        ax = 2 if any(getattr(p, "key", None) == "cycles"
                      for p in path) else 1
        shape = [1] * leaf.ndim
        shape[ax] = leaf.shape[ax]
        mask = (jnp.arange(leaf.shape[ax]) < true_len).reshape(shape)
        out.append(jnp.where(mask, v, 0))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache), out)


# ---------------------------------------------------------------------------
# paged KV store
# ---------------------------------------------------------------------------

def test_paged_kv_round_trip_bitwise(setup):
    cfg = setup["cfg"]
    cache = _filled_cache(cfg)
    kv = PagedKVCache(page_tokens=4)
    kv.commit(0, cache, true_len=10)
    # ceil(10/4) = 3 pages per k/v role per stacked leaf group
    assert kv.stats.role_pages == {"k": 3, "v": 3}
    back = kv.gather(0)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(kv) == 0
    assert kv.stats.device_bytes == 0 and kv.stats.host_bytes == 0


def test_paged_kv_pages_recycle_through_pool(setup):
    cfg = setup["cfg"]
    cache = _filled_cache(cfg)
    kv = PagedKVCache(page_tokens=4)
    kv.commit(0, cache, true_len=10)
    kv.gather(0)                       # pages go back to the free-list
    assert kv.pool.stats.misses > 0 and kv.pool.stats.hits == 0
    kv.commit(1, cache, true_len=10)   # same shapes: all hits
    assert kv.pool.stats.hits == kv.pool.stats.misses
    assert kv.pool.stats.bytes_reused > 0


def test_paged_kv_spill_keeps_bits(setup):
    cfg = setup["cfg"]
    cache = _filled_cache(cfg)
    kv = PagedKVCache(page_tokens=4, device_budget_bytes=1)
    kv.commit(0, cache, true_len=10)
    assert kv.stats.pages_spilled == 6          # whole entry went to host
    assert kv.stats.device_bytes == 0 and kv.stats.host_bytes > 0
    back = kv.gather(0)
    assert kv.stats.pages_fetched == 6
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_kv_total_budget_evicts_lru(setup):
    cfg = setup["cfg"]
    cache = _filled_cache(cfg)
    probe = PagedKVCache(page_tokens=4)
    probe.commit(0, cache, true_len=10)
    one_entry = probe.total_bytes
    kv = PagedKVCache(page_tokens=4, total_budget_bytes=one_entry)
    kv.commit(0, cache, true_len=10)
    evicted = kv.commit(1, cache, true_len=10)
    assert evicted == [0]                       # LRU out, newest stays
    assert 0 not in kv and 1 in kv
    assert kv.stats.evictions == 1


def test_paged_kv_rejects_duplicate_commit(setup):
    cache = _filled_cache(setup["cfg"])
    kv = PagedKVCache(page_tokens=4)
    kv.commit(0, cache, true_len=10)
    with pytest.raises(ValueError, match="already committed"):
        kv.commit(0, cache, true_len=10)


# ---------------------------------------------------------------------------
# engine parity: the one invariant everything else may not bend
# ---------------------------------------------------------------------------

def test_engine_parity_unified(setup):
    reqs = _traffic(setup["cfg"], setup["seed"])
    eng, ex, kv = _engine(setup)
    metrics = run_traffic(eng, reqs)
    assert_parity(reqs, setup["oracle"])
    assert metrics["tokens"] == sum(len(r.tokens) for r in reqs)
    assert all(r.done for r in reqs)


def test_engine_parity_across_host_spill(setup):
    """Device page budget of 1 byte: every parked prefill crosses to host
    DRAM and back — oversubscription must not bend a single bit."""
    reqs = _traffic(setup["cfg"], setup["seed"])
    eng, ex, kv = _engine(setup, ledger_name="spill",
                          device_budget_bytes=1)
    run_traffic(eng, reqs)
    assert kv.stats.pages_spilled > 0 and kv.stats.pages_fetched > 0
    assert kv.stats.device_high_water_bytes <= max(
        1, kv.stats.total_high_water_bytes)
    assert_parity(reqs, setup["oracle"])


def test_engine_parity_across_eviction_requeue(setup):
    """Total budget fits ~one parked entry: the store evicts, the
    scheduler re-queues for a fresh prefill, tokens still match."""
    cfg = setup["cfg"]
    probe = PagedKVCache(page_tokens=4)
    probe.commit(0, _filled_cache(cfg), true_len=10)
    reqs = _traffic(cfg, setup["seed"])
    eng, ex, kv = _engine(setup, ledger_name="evict",
                          total_budget_bytes=probe.total_bytes)
    run_traffic(eng, reqs)
    assert_parity(reqs, setup["oracle"])
    assert ex.ledger.serve_counters.get("evicted", 0) == \
        sum(r.evictions for r in reqs)


def test_engine_parity_discrete_policy(setup):
    """The engine is policy-agnostic: under the discrete emulation every
    region stages through the pools, tokens still match solo jit."""
    reqs = _traffic(setup["cfg"], setup["seed"])
    pol = lm_policy("discrete", setup["cfg"].memory)
    eng, ex, kv = _engine(setup, policy=pol, ledger_name="discrete")
    run_traffic(eng, reqs)
    assert_parity(reqs, setup["oracle"])
    pools = ex.ledger.coverage_report()["pools"]
    assert {"kv_pages", "host_staging", "device_buffer"} <= set(pools)


def test_engine_parity_offload_kv_placer(setup):
    """--offload-kv composes: the KVCachePlacer re-homes appended pages at
    region boundaries while the paged store parks prefills — same bits."""
    reqs = _traffic(setup["cfg"], setup["seed"])
    pol = lm_policy("unified", setup["cfg"].memory,
                    placer=SV.offload_kv_cache(min_bytes=0))
    eng, ex, kv = _engine(setup, policy=pol, ledger_name="offkv")
    run_traffic(eng, reqs)
    assert_parity(reqs, setup["oracle"])
    assert SV.kv_spaces(eng.slot_cache) == {"pinned_host"}


# ---------------------------------------------------------------------------
# scheduler bookkeeping
# ---------------------------------------------------------------------------

def test_engine_serve_section_accounts_lifecycle(setup):
    reqs = _traffic(setup["cfg"], setup["seed"])
    eng, ex, kv = _engine(setup, ledger_name="acct")
    run_traffic(eng, reqs)
    rep = ex.ledger.coverage_report()
    serve = rep["serve"]
    n_decode = sum(1 for r in reqs if r.gen > 1)
    assert serve["submitted"] == len(reqs)
    assert serve["prefills"] == len(reqs)       # warm-up counters reset
    assert serve["admitted"] == n_decode        # gen==1 never takes a slot
    assert serve["retired"] == len(reqs)
    assert serve["decode_tokens"] == sum(r.gen - 1 for r in reqs)
    assert 0 < serve["slot_occupancy"] <= 1
    assert rep["pools"]["kv_pages"]["high_water_bytes"] > 0
    for r in reqs:
        assert r.history[0] == QUEUED and r.history[-1] == DONE


def test_engine_gen_one_finishes_at_prefill(setup):
    eng, ex, kv = _engine(setup, ledger_name="gen1")
    prompt = np.arange(6, dtype=np.int32)
    req = eng.submit(Request(req_id=0, prompt=prompt, gen=1))
    eng.drain()
    assert req.done and len(req.tokens) == 1
    assert req.history == [QUEUED, DONE]        # never PREFILL/DECODE
    assert len(kv) == 0                         # nothing parked


def test_engine_rejects_oversized_and_duplicate(setup):
    eng, ex, kv = _engine(setup, ledger_name="reject")
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        eng.submit(Request(req_id=0, gen=MAX_LEN,
                           prompt=np.zeros(MAX_LEN, np.int32)))
    eng.submit(Request(req_id=1, prompt=np.zeros(4, np.int32), gen=2))
    with pytest.raises(ValueError, match="duplicate req_id"):
        eng.submit(Request(req_id=1, prompt=np.zeros(4, np.int32), gen=2))
    eng.drain()


def test_engine_state_machine_rejects_illegal_transition(setup):
    eng, ex, kv = _engine(setup, ledger_name="fsm")
    req = Request(req_id=0, prompt=np.zeros(4, np.int32), gen=2)
    with pytest.raises(RuntimeError, match="illegal transition"):
        eng._set_state(req, DECODE)             # QUEUED cannot jump slots


# ---------------------------------------------------------------------------
# pool byte accounting (satellite of this PR, used by the report above)
# ---------------------------------------------------------------------------

def test_device_pool_bytes_in_use_and_high_water():
    pool = DeviceBufferPool(min_elems=0)
    a = pool.acquire((8,), jnp.float32)         # 32 B live
    b = pool.acquire((8,), jnp.float32)         # 64 B live
    assert pool.stats.bytes_in_use == 64
    assert pool.stats.high_water_bytes == 64
    pool.release(a)
    assert pool.stats.bytes_in_use == 32 and pool.free_bytes == 32
    c = pool.acquire((8,), jnp.float32)         # free-list hit
    assert pool.stats.hits == 1
    assert pool.stats.bytes_in_use == 64 and pool.free_bytes == 0
    # in_use + free never exceeded the recorded high water
    assert pool.stats.high_water_bytes == 64
    pool.release(b), pool.release(c)
    assert pool.stats.bytes_in_use == 0 and pool.free_bytes == 64


def test_host_pool_bytes_in_use_tracks_outstanding():
    pool = HostStagingPool(min_elems=0)
    a = pool.acquire((100,), np.float32)
    assert pool.stats.bytes_in_use == pool.stats.high_water_bytes > 0
    before = pool.stats.bytes_in_use
    b = pool.acquire((100,), np.float32)
    assert pool.stats.bytes_in_use == 2 * before
    pool.release(a)
    pool.release(b)
    assert pool.stats.bytes_in_use == 0
    assert pool.stats.high_water_bytes == 2 * before
    assert pool.stats.as_dict()["bytes_in_use"] == 0


# ---------------------------------------------------------------------------
# decode_stream sync semantics (pinned down by this PR)
# ---------------------------------------------------------------------------

def _stream_with_sync(setup, sync_every, syncs):
    cfg, mesh, params = setup["cfg"], setup["mesh"], setup["params"]
    prefill, decode, make_cache = SV.build_server(cfg, mesh, 1, 12)
    prompt = np.arange(8, dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompt)[None]}
    logits, cache = prefill(params, batch, make_cache())
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    real = jax.block_until_ready

    def counting(x):
        syncs.append(1)
        return real(x)

    jax.block_until_ready = counting
    try:
        toks, _ = SV.decode_stream(decode, params, tok, cache, 8, 4,
                                   sync_every=sync_every)
    finally:
        jax.block_until_ready = real
    return [int(np.asarray(t)[0]) for t in toks]


@pytest.mark.parametrize("sync_every,expected_syncs", [
    (0, 1),     # never mid-stream: exactly the one final sync
    (-3, 1),    # negative = same contract (used to alias per-token sync)
    (1, 4),     # retired per-token sync: 3 mid-stream + 1 final
])
def test_decode_stream_sync_every_contract(setup, sync_every,
                                           expected_syncs):
    syncs = []
    toks = _stream_with_sync(setup, sync_every, syncs)
    assert len(syncs) == expected_syncs
    # sync cadence is scheduling, not math
    ref = _stream_with_sync(setup, 0, [])
    assert toks == ref


def test_logit_parity_accepts_engine_tokens_rejects_others(setup):
    """The launcher's tolerance check: every engine token passes (they
    equal the solo decode on this backend, so nothing diverges and no gap
    opens), and a stream holding the reference's least likely token
    fails."""
    from repro.serve.scheduler import batch_for_prompt
    from repro.serve.traffic import assert_logit_parity
    cfg, mesh, params = setup["cfg"], setup["mesh"], setup["params"]
    reqs = _traffic(cfg, setup["seed"])
    eng, ex, kv = _engine(setup, ledger_name="logits")
    run_traffic(eng, reqs)
    stats = assert_logit_parity(cfg, mesh, params, reqs, setup["oracle"],
                                MAX_LEN)
    assert stats == {"tokens": sum(len(r.tokens) for r in reqs),
                     "diverged": 0, "gap_max": 0.0}
    prefill, _, make_cache = SV.build_server(cfg, mesh, 1, MAX_LEN)
    r = reqs[0]
    logits, _ = prefill(params, batch_for_prompt(cfg, r.prompt),
                        make_cache())
    r.tokens = [int(jnp.argmin(logits[0, -1]))] + list(r.tokens[1:])
    with pytest.raises(AssertionError, match="bf16 spacings"):
        assert_logit_parity(cfg, mesh, params, reqs, setup["oracle"],
                            MAX_LEN)
