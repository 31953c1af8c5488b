"""Memory oversubscription: run models and grids that don't fit (ROADMAP 4).

The invariant suite behind ``repro.core.oversub``: a MemoryBudget below
the working set degrades every workload through spill / paging / chunked
staging instead of OOMing, and NEVER changes values — each budgeted run
is bit-identical to its unbudgeted reference (the §2 parity contract).
Covers the three budgeted workloads of fig_oversub (KV serving, MoE
expert paging, CFD staged replay), the Hypothesis property suite over
random PagedKVCache interleavings, the engine drain/pool-accounting
regression, and the same-seed traffic determinism contract.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # conftest stubs this, but be safe
    HAVE_HYPOTHESIS = False

from repro.configs.reduced import reduced as make_reduced
from repro.configs.registry import get_config
from repro.core import umem
from repro.core.ledger import Ledger
from repro.core.oversub import (MIN_CHUNK_BYTES, BudgetedPlacer,
                                MemoryBudget, workload_bytes)
from repro.core.pool import DeviceBufferPool
from repro.core.regions import (DiscretePolicy, Executor, UnifiedPolicy,
                                region)
from repro.core.umem import MemSpace
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serve import (PagedKVCache, ServeEngine, make_traffic,
                         run_traffic, solo_reference)
from repro.serve.traffic import assert_parity

MAX_LEN = 16


# ---------------------------------------------------------------------------
# MemoryBudget unit contract
# ---------------------------------------------------------------------------

def test_budget_charge_release_high_water():
    b = MemoryBudget(100)
    assert b.charge(60) and b.stats.charged_bytes == 60
    assert not b.charge(60)              # lands over: pressure, no raise
    assert b.over and b.stats.pressure_events == 1
    assert b.stats.high_water_bytes == 120
    b.release(60)
    assert not b.over and b.stats.charged_bytes == 60
    b.release(1000)                      # floors at zero, never negative
    assert b.stats.charged_bytes == 0
    assert b.stats.high_water_bytes == 120


def test_budget_for_ratio_headroom_and_utilization():
    b = MemoryBudget.for_ratio(1000, 4.0)
    assert b.limit_bytes == 250
    assert b.oversubscription_ratio(1000) == 4.0
    assert b.headroom() == 250
    b.charge(200)
    assert b.headroom() == 50 and b.utilization() == 0.8
    # ratio 1 = the everything-fits reference point
    assert MemoryBudget.for_ratio(1000, 1.0).limit_bytes == 1000
    # unlimited budget: everything fits by definition
    u = MemoryBudget()
    assert u.fits(10**12) and u.headroom() is None
    assert u.oversubscription_ratio(10**12) == 1.0
    with pytest.raises(ValueError):
        MemoryBudget.for_ratio(1000, 0)
    with pytest.raises(ValueError):
        MemoryBudget(0)


def test_budget_admit_denies_and_counts_spill():
    b = MemoryBudget(100)
    assert b.admit(80)
    assert not b.admit(80)               # would exceed: denied, not charged
    assert b.stats.charged_bytes == 80
    assert b.stats.denials == 1 and b.stats.spilled_bytes == 80
    # consult: advisory, never charges
    assert not b.consult(80) and b.consult(10)
    assert b.stats.charged_bytes == 80


def test_budget_staging_chunk_bytes():
    assert MemoryBudget().staging_chunk_bytes() is None
    assert MemoryBudget(1 << 20).staging_chunk_bytes() == (1 << 20) // 4
    # tiny budgets floor at MIN_CHUNK_BYTES: chunking below a page of
    # work costs more dispatches than it saves
    assert MemoryBudget(16).staging_chunk_bytes() == MIN_CHUNK_BYTES


# ---------------------------------------------------------------------------
# DeviceBufferPool x budget: accounting agrees byte-for-byte
# ---------------------------------------------------------------------------

def test_device_pool_charges_and_releases_budget():
    b = MemoryBudget(64)
    pool = DeviceBufferPool(min_elems=0, budget=b)
    x = pool.acquire((8,), jnp.float32)          # 32 B
    assert b.stats.charged_bytes == pool.stats.bytes_in_use == 32
    y = pool.acquire((16,), jnp.float32)         # 96 B: over, pressure
    assert b.stats.charged_bytes == pool.stats.bytes_in_use == 96
    assert b.stats.pressure_events == 1
    pool.release(x)
    pool.release(y)
    assert b.stats.charged_bytes == pool.stats.bytes_in_use == 0
    assert b.stats.high_water_bytes == 96
    # free-list hits charge too: a reacquired buffer is device-resident
    z = pool.acquire((8,), jnp.float32)
    assert pool.stats.hits == 1 and b.stats.charged_bytes == 32
    pool.release(z)


def test_device_pool_skips_budget_below_threshold():
    b = MemoryBudget(1024)
    pool = DeviceBufferPool(min_elems=100, budget=b)
    x = pool.acquire((8,), jnp.float32)          # unpooled: not charged
    assert pool.stats.unpooled == 1 and b.stats.charged_bytes == 0
    pool.release(x)
    assert b.stats.charged_bytes == 0


# ---------------------------------------------------------------------------
# Placement axis under a budget
# ---------------------------------------------------------------------------

def test_tree_place_budgeted_splits_and_preserves_values():
    b = MemoryBudget(40)
    tree = {"a": jnp.arange(8, dtype=jnp.float32),    # 32 B: admitted
            "b": jnp.arange(8, dtype=jnp.float32)}    # 32 B: spilled
    placed = umem.tree_place_budgeted(tree, b)
    assert b.stats.charged_bytes == 32
    assert b.stats.denials == 1 and b.stats.spilled_bytes == 32
    for k in tree:                                    # placement, not math
        np.testing.assert_array_equal(np.asarray(tree[k]),
                                      np.asarray(placed[k]))


def test_budgeted_placer_demotes_hints_bitwise():
    ldg = Ledger("bp")

    @region("bp_scale", ledger=ldg,
            placement={0: MemSpace.DEVICE, 1: MemSpace.DEVICE})
    def bp_scale(a, x):
        return a * x

    a = jnp.linspace(0.0, 1.0, 8)                     # 32 B: within budget
    x = jnp.linspace(1.0, 2.0, 8 * 64).reshape(64, 8)  # 2 KiB: demoted
    ref = Executor(UnifiedPolicy(), Ledger("bp_ref")).run(bp_scale, a, x)
    budget = MemoryBudget(256)
    pol = UnifiedPolicy(placer=BudgetedPlacer(budget=budget))
    out = Executor(pol, Ledger("bp_out")).run(bp_scale, a, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # consult-only: hints are per-call transients, nothing stays charged
    assert budget.stats.charged_bytes == 0
    assert budget.stats.admitted >= 1 and budget.stats.denials >= 1


# ---------------------------------------------------------------------------
# Workload (a): MoE decode with host-resident experts paged per token
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moe_setup():
    # qwen3-moe-30b-a3b structure at test scale, but with a sparse router
    # (16 experts, top-2) so paging is meaningful — the reduced() cap
    # (8 experts, top-8) selects every expert every token
    cfg = make_reduced(get_config("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=16, top_k=2,
                                     d_ff=32))
    p = init_params(jax.random.PRNGKey(0), M.moe_specs(cfg))
    xs = [jax.random.normal(jax.random.PRNGKey(10 + t),
                            (1, 1, cfg.d_model), cfg.compute_dtype)
          for t in range(6)]             # a 6-token decode stream
    return {"cfg": cfg, "p": p, "xs": xs}


def _paged_stream(s, budget):
    pager = M.ExpertPager(s["p"], s["cfg"], budget=budget)
    ys = []
    for x in s["xs"]:
        y, _ = M.moe_decode_paged(pager, x, s["cfg"])
        if budget is not None:           # the invariant the LRU maintains
            assert pager.resident_bytes <= budget.limit_bytes
        ys.append(np.asarray(y))
    return pager, ys


def test_moe_paged_budgeted_bitwise_vs_resident(moe_setup):
    """The tentpole parity bar: a 4x-oversubscribed expert working set
    produces bit-identical outputs — paging changes residency, not math."""
    pager_ref, ref = _paged_stream(moe_setup, None)
    fp = pager_ref.footprint_bytes
    for ratio in (2.0, 4.0):
        budget = MemoryBudget.for_ratio(fp, ratio)
        pager, ys = _paged_stream(moe_setup, budget)
        for a, b in zip(ref, ys):
            np.testing.assert_array_equal(a, b)
        assert pager.stats.fetches > 0
        assert budget.stats.high_water_bytes <= budget.limit_bytes \
            + pager.slab_bytes           # transient: one slab mid-evict


def test_moe_paged_matches_dense_oracle(moe_setup):
    s = moe_setup
    pager = M.ExpertPager(s["p"], s["cfg"])
    for x in s["xs"][:2]:
        y, aux = M.moe_decode_paged(pager, x, s["cfg"])
        yr, auxr = M.moe_ref(s["p"], x, s["cfg"])
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(float(aux), float(auxr), rtol=1e-5)


def test_expert_pager_lru_and_accounting(moe_setup):
    s = moe_setup
    pager = M.ExpertPager(
        s["p"], s["cfg"],
        budget=MemoryBudget(2 * _slab_bytes(s)))     # room for 2 slabs
    pager.get(0), pager.get(1)
    assert pager.stats.fetches == 2 and pager.stats.evictions == 0
    pager.get(0)                                     # touch: 0 is now MRU
    assert pager.stats.hits == 1
    pager.get(2)                                     # evicts LRU = 1
    assert pager.stats.evictions == 1
    assert set(pager._resident) == {0, 2}
    assert pager.budget.stats.charged_bytes == pager.resident_bytes
    pager.drop()
    assert pager.budget.stats.charged_bytes == 0 and not pager._resident


def _slab_bytes(s):
    return sum(int(s["p"][k][0].nbytes) for k in M.EXPERT_KEYS)


def test_moe_prefetch_parity_and_overlap(moe_setup):
    """The one-step slab lookahead (AsyncExecutor's contract applied to
    expert paging): identical fetch/hit/eviction accounting and
    bit-identical outputs vs the lookahead-off pager, with the hidden
    fetch time surfaced on the pager stats and the ledger gauge."""
    s = moe_setup
    led = Ledger("serve")
    pon = M.ExpertPager(s["p"], s["cfg"])            # lookahead default on
    poff = M.ExpertPager(s["p"], s["cfg"], lookahead=False)
    for x in s["xs"]:
        y1, _ = M.moe_decode_paged(pon, x, s["cfg"], ledger=led)
        y0, _ = M.moe_decode_paged(poff, x, s["cfg"])
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
    on, off = pon.stats, poff.stats
    # prefetch moves the same bytes at a different time — the paging
    # ledger cannot tell the difference
    assert (on.fetches, on.hits, on.evictions, on.bytes_fetched) == \
        (off.fetches, off.hits, off.evictions, off.bytes_fetched)
    assert on.prefetch_hits > 0 and off.prefetch_hits == 0
    assert on.prefetch_overlap_s >= 0.0
    assert "moe_prefetch_overlap_s" in led.serve_gauges
    assert led.serve_counters.get("moe_prefetch_hit") == on.prefetch_hits
    pon.drop()
    assert not pon._pending and not pon._resident


def test_moe_prefetch_budgeted_charges_on_install(moe_setup):
    """A prefetched slab only hits the MemoryBudget when get() installs
    it, so the budget invariants (and evictions) are unchanged by the
    lookahead."""
    s = moe_setup
    budget = MemoryBudget(2 * _slab_bytes(s))
    pager = M.ExpertPager(s["p"], s["cfg"], budget=budget)
    ys = []
    for x in s["xs"]:
        y, _ = M.moe_decode_paged(pager, x, s["cfg"])
        assert pager.resident_bytes <= budget.limit_bytes
        ys.append(np.asarray(y))
    ref, refs = _paged_stream(s, None)
    for a, b in zip(refs, ys):
        np.testing.assert_array_equal(a, b)
    assert pager.stats.evictions > 0             # the budget really bound
    pager.drop()
    assert budget.stats.charged_bytes == 0


# ---------------------------------------------------------------------------
# Workload (c): CFD grids beyond device capacity via budgeted staged replay
# ---------------------------------------------------------------------------

def test_cfd_budgeted_chunked_staging_bitwise():
    """A captured SIMPLE step replayed under a discrete policy whose
    budget is 1/4 the state footprint: staging happens in budget-sized
    slabs (chunks counted), fields stay bit-identical to the unbudgeted
    discrete replay."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid((12, 12, 12)), nu=0.1, inner_max=6)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)
    prog = app.capture_step(st)
    s_ref, _ = app.replay_steps(prog, st, 2, Executor(DiscretePolicy()))
    fp = workload_bytes(st)
    assert fp > 0
    budget = MemoryBudget.for_ratio(fp, 4.0)
    assert budget.staging_chunk_bytes() < 12 * 12 * 12 * 4  # < one field
    s_b, _ = app.replay_steps(prog, st, 2,
                              Executor(DiscretePolicy(budget=budget)))
    for name in ("u", "v", "w", "p"):
        np.testing.assert_array_equal(np.asarray(getattr(s_ref, name)),
                                      np.asarray(getattr(s_b, name)))
    assert budget.stats.staging_chunks > 0
    assert budget.stats.pressure_events > 0          # it really didn't fit


def test_sharded_scatter_respects_staging_budget():
    """The sharded+staged replay path: ShardExecutor's host->APUs scatter
    chunks through the policy budget on a degenerate 1-APU mesh, matching
    the unbudgeted sharded replay bit-for-bit."""
    from repro.core.program import capture
    from repro.core.shard_program import shard_program
    ldg = Ledger("oversub_shard")
    grid = (16, 16, 16)                  # 16 KiB fields: > min chunk

    @region("ov_scale", ledger=ldg)
    def ov_scale(d, x):
        return d * x

    def step(run, d, x):
        return run(ov_scale, d, run(ov_scale, d, x))

    d = jnp.linspace(1.0, 2.0, int(np.prod(grid))).reshape(grid)
    x = jnp.full(grid, 0.3, jnp.float32)
    prog = capture(step, d, x, name="ov3d")
    from repro.launch.mesh import make_apu_mesh
    mesh = make_apu_mesh(1)
    ref = shard_program(prog, mesh, DiscretePolicy()).replay(d, x)
    budget = MemoryBudget(16384)         # chunk = 4 KiB < one 16 KiB field
    out = shard_program(prog, mesh,
                        DiscretePolicy(budget=budget)).replay(d, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    assert budget.stats.staging_chunks > 1


# ---------------------------------------------------------------------------
# Workload (b): KV caches beyond the device budget (store-level contract;
# the full-traffic engine runs live in the engine section below)
# ---------------------------------------------------------------------------

def _toy_cache(rng, S, true_len):
    """A synthetic k/v cache tree (the role keying PagedKVCache pages on)
    with the init_cache-style zero tail beyond true_len."""
    def leaf():
        a = rng.random((1, S, 4)).astype(np.float32)
        a[:, true_len:] = 0
        return a
    return {"k": jnp.asarray(leaf()), "v": jnp.asarray(leaf()),
            "pos": jnp.full((1,), true_len, jnp.int32)}


def test_paged_kv_memory_budget_drives_spill_bitwise():
    rng = np.random.default_rng(3)
    cache = _toy_cache(rng, 12, 10)
    budget = MemoryBudget(1)             # nothing device-resident fits
    kv = PagedKVCache(page_tokens=4, budget=budget)
    kv.commit(0, cache, true_len=10)
    assert kv.stats.pages_spilled == 6 and kv.stats.device_bytes == 0
    assert budget.stats.charged_bytes == 0           # spill released it
    assert budget.stats.pressure_events >= 1
    back = kv.gather(0)
    for key in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(cache[key]),
                                      np.asarray(back[key]))
    assert budget.stats.charged_bytes == 0


def test_paged_kv_tightest_of_two_budgets_wins():
    rng = np.random.default_rng(4)
    cache = _toy_cache(rng, 12, 10)
    # explicit device_budget_bytes is looser than the MemoryBudget: the
    # budget's limit governs
    kv = PagedKVCache(page_tokens=4, device_budget_bytes=1 << 30,
                      budget=MemoryBudget(1))
    kv.commit(0, cache, true_len=10)
    assert kv.stats.pages_spilled == 6
    # and the other way around
    kv2 = PagedKVCache(page_tokens=4, device_budget_bytes=1,
                       budget=MemoryBudget(1 << 30))
    kv2.commit(0, cache, true_len=10)
    assert kv2.stats.pages_spilled == 6


# ---------------------------------------------------------------------------
# Satellite: Hypothesis property suite — random interleavings of
# commit/spill/evict/requeue vs an unpaged reference cache
# ---------------------------------------------------------------------------

def _run_interleaving(page_tokens, dev_budget, tot_entries, seed, ops):
    """The satellite-1 property: under ANY interleaving of commit /
    budget-spill / budget-evict / requeue with random page sizes and
    budgets, gather stays bitwise equal to the kept-original reference
    tree, and page/byte accounting never goes negative."""
    rng = np.random.default_rng(seed)
    trees = {}                           # rid -> (numpy tree, true_len)
    for rid in range(6):
        S = int(rng.integers(4, 17))
        tl = int(rng.integers(1, S + 1))
        trees[rid] = (_toy_cache(rng, S, tl), tl)
    one_entry = None
    if tot_entries is not None:
        probe = PagedKVCache(page_tokens=page_tokens)
        probe.commit(0, trees[0][0], true_len=trees[0][1])
        one_entry = probe.total_bytes * tot_entries
        probe.free(0)
    kv = PagedKVCache(page_tokens=page_tokens,
                      device_budget_bytes=dev_budget,
                      total_budget_bytes=one_entry)
    parked = set()

    def check_invariants():
        s = kv.stats
        assert s.device_bytes >= 0 and s.host_bytes >= 0
        assert kv.pool.stats.bytes_in_use >= 0
        assert s.pages_released <= s.pages_committed
        if dev_budget is not None:       # spill always possible on CPU
            assert s.device_bytes <= dev_budget

    def check_bits(rid, back):
        ref = trees[rid][0]
        for key in ref:
            np.testing.assert_array_equal(np.asarray(ref[key]),
                                          np.asarray(back[key]))

    for op, rid in ops:
        if op == "commit" and rid not in parked:
            evicted = kv.commit(rid, trees[rid][0],
                                true_len=trees[rid][1])
            parked.add(rid)
            for ev in evicted:           # evict = requeue: commit later ok
                parked.discard(ev)
        elif op == "gather" and rid in parked:
            check_bits(rid, kv.gather(rid))
            parked.discard(rid)
        elif op == "free" and rid in parked:
            kv.free(rid)
            parked.discard(rid)
        elif op == "touch":
            kv.touch(rid)
        check_invariants()

    for rid in sorted(parked):           # drain: every survivor bit-exact
        check_bits(rid, kv.gather(rid))
    assert kv.stats.device_bytes == 0 and kv.stats.host_bytes == 0
    assert kv.pool.stats.bytes_in_use == 0


@settings(max_examples=20, deadline=None)
@given(
    page_tokens=st.integers(min_value=1, max_value=6),
    dev_budget=st.sampled_from([None, 1, 256, 4096]),
    tot_entries=st.sampled_from([None, 1, 3]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    ops=st.lists(
        st.tuples(st.sampled_from(["commit", "gather", "free", "touch"]),
                  st.integers(min_value=0, max_value=5)),
        min_size=1, max_size=24),
)
def test_paged_kv_random_interleavings_property(page_tokens, dev_budget,
                                                tot_entries, seed, ops):
    _run_interleaving(page_tokens, dev_budget, tot_entries, seed, ops)


def test_paged_kv_random_interleavings_seeded():
    """Deterministic fallback for the property above: the same invariant
    over 15 seeded random draws, so the interleaving contract is exercised
    even where hypothesis is unavailable (the conftest stub turns the
    @given test into a SKIP there)."""
    rng = np.random.default_rng(7)
    for trial in range(15):
        page_tokens = int(rng.integers(1, 7))
        dev_budget = [None, 1, 256, 4096][trial % 4]
        tot_entries = [None, 1, 3][trial % 3]
        n_ops = int(rng.integers(4, 25))
        ops = [(["commit", "gather", "free", "touch"][int(rng.integers(4))],
                int(rng.integers(6))) for _ in range(n_ops)]
        _run_interleaving(page_tokens, dev_budget, tot_entries,
                          int(rng.integers(2**31)), ops)


# ---------------------------------------------------------------------------
# Engine-level: budgeted traffic, drain accounting, seed determinism
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def eng_setup(traffic_seed):
    cfg = make_reduced(get_config("tinyllama-1.1b"))
    from repro.launch.mesh import make_smoke_mesh
    mesh = make_smoke_mesh()
    params = T.init(jax.random.PRNGKey(0), cfg)
    reqs = _traffic(cfg, traffic_seed)
    oracle, _ = solo_reference(cfg, mesh, params, reqs, MAX_LEN)
    return {"cfg": cfg, "mesh": mesh, "params": params, "oracle": oracle,
            "seed": traffic_seed}


def _traffic(cfg, seed, id_base=0):
    reqs = make_traffic(seed=seed, n_requests=4, vocab=cfg.vocab,
                        arrival_rate=2.0, prompt_lens=(6, 10),
                        gen_lens=(1, 5))
    for r in reqs:
        r.req_id += id_base
    return reqs


def _engine(s, budget=None, ledger_name="oversub", **kv_kwargs):
    ex = Executor(UnifiedPolicy(), Ledger(ledger_name))
    kv = PagedKVCache(page_tokens=4, budget=budget, **kv_kwargs)
    eng = ServeEngine(s["cfg"], s["mesh"], s["params"], ex,
                      max_len=MAX_LEN, n_slots=2, kv=kv)
    return eng, ex, kv


def _kv_footprint(s, n_slots=2):
    probe = PagedKVCache(page_tokens=4)
    probe.commit(0, T.init_cache(s["cfg"], 1, MAX_LEN), true_len=MAX_LEN)
    fp = probe.total_bytes * n_slots
    probe.free(0)
    return fp


def test_engine_parity_under_oversubscription(eng_setup):
    """Tentpole workload (b) end-to-end: real traffic against a KV budget
    a quarter of the working set (ratio 2 exactly equals the parked-page
    peak for this traffic, so 4x is the first ratio that forces spill) —
    spill traffic flows, the budget gauges land in the ledger, and every
    token matches the solo oracle bit-for-bit."""
    s = eng_setup
    budget = MemoryBudget.for_ratio(_kv_footprint(s), 4.0, name="kv")
    reqs = _traffic(s["cfg"], s["seed"])
    eng, ex, kv = _engine(s, budget=budget)
    run_traffic(eng, reqs)
    assert_parity(reqs, s["oracle"])
    assert kv.stats.pages_spilled > 0
    gauges = ex.ledger.coverage_report()["serve"]
    assert gauges["kv_budget_limit_bytes"] == budget.limit_bytes
    assert gauges["kv_budget_high_water_bytes"] > 0


def test_engine_drain_restores_pool_baseline(eng_setup):
    """Satellite regression: after a run fully drains, the KV pool's
    bytes_in_use returns to its pre-run baseline and high_water_bytes is
    monotone — the double-release/leak tripwire for the spill path."""
    s = eng_setup
    eng, ex, kv = _engine(s, ledger_name="drain",
                          device_budget_bytes=1)     # force spill traffic
    baseline = kv.pool.stats.bytes_in_use
    run_traffic(eng, _traffic(s["cfg"], s["seed"]))
    assert kv.stats.pages_spilled > 0
    assert len(kv) == 0
    assert kv.pool.stats.bytes_in_use == baseline
    hw1 = kv.pool.stats.high_water_bytes
    assert hw1 > 0
    # second wave on the SAME engine (fresh ids): baseline again, high
    # water never decreases
    run_traffic(eng, _traffic(s["cfg"], s["seed"], id_base=100),
                warmup=False)
    assert kv.pool.stats.bytes_in_use == baseline
    assert kv.pool.stats.high_water_bytes >= hw1


def test_same_seed_traffic_is_reproducible(eng_setup):
    """Satellite: the threaded seed fixture makes traffic runs
    deterministic — two same-seed engine runs produce identical token
    streams, and make_traffic itself is a pure function of the seed."""
    s = eng_setup
    a = make_traffic(seed=s["seed"], n_requests=4, vocab=s["cfg"].vocab)
    b = make_traffic(seed=s["seed"], n_requests=4, vocab=s["cfg"].vocab)
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
        assert (ra.gen, ra.arrival_tick) == (rb.gen, rb.arrival_tick)
    streams = []
    for _ in range(2):
        reqs = _traffic(s["cfg"], s["seed"])
        eng, ex, kv = _engine(s, ledger_name="det")
        run_traffic(eng, reqs)
        streams.append([list(map(int, r.tokens)) for r in reqs])
    assert streams[0] == streams[1]
