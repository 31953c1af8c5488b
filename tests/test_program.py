"""Captured region programs (repro.core.program): capture fidelity, replay
parity across policies (sync Executor == AsyncExecutor), overlap/staging
accounting, batched replay, and pooled buffer rotation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ledger import Ledger
from repro.core.pool import BufferRotation, DeviceBufferPool
from repro.core.program import (AsyncExecutor, In, Lit, Ref, RegionProgram,
                                capture)
from repro.core.regions import (AdaptivePolicy, DiscretePolicy, Executor,
                                HostPolicy, UnifiedPolicy, region)

N = 1 << 15           # big enough to exceed pool/placement thresholds


def make_program(ledger=None):
    """A small solver-shaped program: dataflow edges, a host-extracted
    scalar (frozen as a constant), and multi-output regions."""
    kw = dict(ledger=ledger or Ledger("prog_test"))

    @region("scale", **kw)
    def scale(d, x):
        return d * x

    @region("saxpy", **kw)
    def saxpy(a, x, y):
        return y - a * x

    @region("split", **kw)
    def split(x):
        return x * 0.5, x * 2.0

    @region("dot", **kw)
    def dot(x, y):
        return jnp.sum(x * y)

    def step(run, d, x, b):
        r = run(saxpy, 1.0, run(scale, d, x), b)
        lo, hi = run(split, r)
        s = float(run(dot, lo, hi))            # frozen control-flow scalar
        return run(saxpy, s / (abs(s) + 1.0), lo, hi)

    d = jnp.linspace(1.0, 2.0, N)
    x = jnp.full((N,), 0.3, jnp.float32)
    b = jnp.linspace(0.0, 1.0, N)
    return capture(step, d, x, b, name="mini"), (d, x, b), step


def test_capture_records_dataflow_and_constants():
    prog, (d, x, b), _ = make_program()
    assert len(prog) == 5
    assert prog.n_inputs == 3
    kinds = [type(l) for op in prog.ops for l in op.leaves]
    assert Ref in kinds and In in kinds and Lit in kinds
    # output of the program is the last op's output, not a constant
    assert isinstance(prog.out_leaves[0], Ref)
    assert "5 ops" in prog.summary()


@pytest.mark.parametrize("make_policy", [
    UnifiedPolicy, HostPolicy, DiscretePolicy,
    lambda: AdaptivePolicy(cutoff=1024)])
def test_async_matches_sync_under_every_policy(make_policy):
    prog, (d, x, b), _ = make_program()
    sync = Executor(make_policy())
    asyn = AsyncExecutor(make_policy())
    out_s = prog.replay(sync, d, x, b)
    out_a = prog.replay(asyn, d, x, b)
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_a))


def test_replay_with_fresh_inputs_recomputes_dataflow():
    prog, (d, x, b), step = make_program()
    ex = Executor(UnifiedPolicy())
    x2 = jnp.full((N,), 0.9, jnp.float32)
    out = prog.replay(ex, d, x2, b)
    # the array dataflow reacts to the new input (the frozen dot-scalar is
    # capture's documented constant; all Ref-edges recompute)
    base = prog.replay(ex, d, x, b)
    assert not np.allclose(np.asarray(out), np.asarray(base))


def test_replay_rejects_mismatched_structure():
    prog, (d, x, b), _ = make_program()
    with pytest.raises(ValueError, match="structure"):
        prog.replay(Executor(UnifiedPolicy()), d, x)


def test_async_discrete_overlaps_and_accounts():
    prog, (d, x, b), _ = make_program()
    asyn = AsyncExecutor(DiscretePolicy())
    sync = Executor(DiscretePolicy())
    out_a = prog.replay(asyn, d, x, b)
    out_s = prog.replay(sync, d, x, b)
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_a))
    rep_a, rep_s = asyn.report(), sync.report()
    # same staged bytes whether or not staging was overlapped
    assert rep_a["staging_s"] > 0
    rows_a = {r["name"]: r for r in asyn.ledger.table()}
    rows_s = {r["name"]: r for r in sync.ledger.table()}
    for name, r in rows_a.items():
        assert r["staging_bytes"] == rows_s[name]["staging_bytes"], name
        # overlap can never exceed the staging it hides
        assert 0.0 <= r["overlap_s"] <= r["staging_s"] + 1e-9, name
    assert rep_a["overlap_s"] <= rep_a["staging_s"]
    assert rep_a["overlap_fraction"] == pytest.approx(
        rep_a["overlap_s"] / rep_a["staging_s"])
    assert rep_a["staging_saved_s"] == rep_a["overlap_s"]
    # sync replay reports no overlap at all
    assert rep_s["overlap_s"] == 0.0 and rep_s["overlap_fraction"] == 0.0


def test_null_stager_policies_report_zero_overlap():
    prog, (d, x, b), _ = make_program()
    asyn = AsyncExecutor(UnifiedPolicy())
    prog.replay(asyn, d, x, b)
    rep = asyn.report()
    assert rep["staging_s"] == 0.0 and rep["overlap_fraction"] == 0.0


def test_replay_batch_matches_sequential_replays():
    prog, (d, x, b), _ = make_program()
    ex = Executor(UnifiedPolicy())
    B = 3
    ds = jnp.stack([d] * B)
    xs = jnp.stack([x + 0.01 * i for i in range(B)])
    bs = jnp.stack([b] * B)
    batched = prog.replay_batch(ds, xs, bs, executor=ex)
    seq = jnp.stack([prog.replay(ex, ds[i], xs[i], bs[i]) for i in range(B)])
    np.testing.assert_allclose(np.asarray(batched), np.asarray(seq),
                               rtol=1e-6, atol=1e-6)
    # accounted as one ledger row
    assert any(name.startswith("mini[batch]") for name in ex.ledger.regions)


def test_async_executor_run_falls_back_to_sync():
    ldg = Ledger("fallback")

    @region("twice", ledger=ldg)
    def twice(x):
        return x * 2.0

    asyn = AsyncExecutor(UnifiedPolicy(), ldg)
    out = asyn.run(twice, jnp.ones((8,)))
    np.testing.assert_array_equal(np.asarray(out), 2.0 * np.ones((8,)))
    assert "+async" in asyn.report()["mode"]


def test_cavity_step_capture_parity():
    """The acceptance-criterion scenario at test scale: one captured SIMPLE
    step, sync vs async DiscretePolicy replay, identical fields, positive
    overlap fraction in coverage_report()."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid((8, 8, 8)), nu=0.1, inner_max=6)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)
    prog = app.capture_step(st)
    assert len(prog) > 20
    sync = Executor(DiscretePolicy())
    asyn = AsyncExecutor(DiscretePolicy())
    s_sync, _ = app.replay_steps(prog, st, 2, sync)
    s_asyn, _ = app.replay_steps(prog, st, 2, asyn)
    for a, b in zip((s_sync.u, s_sync.v, s_sync.w, s_sync.p),
                    (s_asyn.u, s_asyn.v, s_asyn.w, s_asyn.p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rep = asyn.report()
    assert rep["staging_s"] > 0
    assert rep["overlap_fraction"] > 0, rep
    assert rep["staging_saved_s"] > 0


def test_cavity_capture_keeps_krylov_scalars_as_dataflow():
    """The solver's Krylov scalars stay region outputs, so capture records
    them as Ref edges (no host float frozen as a Lit) and a chained replay
    computes real SIMPLE iterations: two replayed steps match two eager
    ones."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid((8, 8, 8)), nu=0.1, tol_u=0.0, tol_p=0.0,
                       inner_max=4)
    app = SimpleFoam(cfg)
    st, _, _ = app.run_steps(init_state(cfg), 1)
    prog = app.capture_step(st)
    lits = [d.value for op in prog.ops for d in op.leaves
            if isinstance(d, Lit)]
    assert not any(isinstance(v, (float, np.floating)) for v in lits)
    reductions = {k for k, op in enumerate(prog.ops)
                  if op.region.name.split("#")[0] in ("dot", "sumMag")}
    consumed = {d.op for op in prog.ops for d in op.leaves
                if isinstance(d, Ref)}
    # four solves, each a |b| sum and four dots an iteration
    assert len(reductions) == 4 * (1 + 4 * 4) and reductions <= consumed
    replayed, _ = app.replay_steps(prog, st, 2, Executor(UnifiedPolicy()))
    eager = st
    for _ in range(2):
        eager, _ = app.time_step(eager)
    for a, b in zip((replayed.u, replayed.v, replayed.w, replayed.p),
                    (eager.u, eager.v, eager.w, eager.p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# PR-2 flake regression: buffer-aliasing/recycling race
# ---------------------------------------------------------------------------

def test_replay_results_survive_forced_recycling_20_of_20():
    """PR-2 flake regression (deterministic, no load dependence): the race
    was a staged-out result page returning to the host pool while its
    host-wrap copy could still be in flight — a later replay's ``copyto``
    into the recycled page then corrupted the FIRST replay's outputs.

    This harness forces the reuse instead of relying on CPU load: sync and
    async executors share ONE DiscretePolicy, so every replay recycles the
    same host pages, device buffers, and rotation banks as the previous
    one (pool hit counters prove it).  Both PR-2 parity assertions must
    hold 20/20, and earlier outputs must stay bit-identical to the
    snapshots taken before the pools were churned again."""
    prog, (d, x, b), _ = make_program()
    pol = DiscretePolicy()
    sync = Executor(pol)
    asyn = AsyncExecutor(pol)
    for i in range(20):
        out_a = prog.replay(asyn, d, x, b)
        snap_a = np.array(out_a)              # snapshot BEFORE pool churn
        out_s = prog.replay(sync, d, x, b)
        snap_s = np.array(out_s)
        # the two PR-2 parity assertions
        np.testing.assert_array_equal(snap_s, snap_a,
                                      err_msg=f"round {i}: sync != async")
        # replay N's outputs survive replay N+1's recycling of the pools
        out_a2 = prog.replay(asyn, d, x, b)
        np.testing.assert_array_equal(
            np.asarray(out_a), snap_a,
            err_msg=f"round {i}: first replay's outputs corrupted")
        np.testing.assert_array_equal(
            np.asarray(out_s), snap_s,
            err_msg=f"round {i}: sync replay's outputs corrupted")
        np.testing.assert_array_equal(np.asarray(out_a2), snap_a)
    stager = pol.stager
    # the harness really did recycle: pooled pages and device buffers hit
    assert stager.host_pool.stats.hits > 0
    assert stager.device_pool.stats.hits > 0


def test_migrate_out_pages_not_recycled_while_copy_in_flight():
    """Unit form of the same race: consecutive same-size stage-outs must
    never overwrite an earlier result, whether the host wrap aliased the
    pooled page (finalize-owned) or copied it (released only after the
    copy completed)."""
    from repro.core.regions import MigrationStager
    stager = MigrationStager()
    outs = [stager._migrate_out(jnp.full((N,), float(i))) for i in range(8)]
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o), float(i))


# ---------------------------------------------------------------------------
# BufferRotation
# ---------------------------------------------------------------------------

def test_rotation_banks_are_disjoint_and_retire_releases():
    pool = DeviceBufferPool(min_elems=1)
    rot = BufferRotation(pool, depth=2)
    a = rot.acquire((N,), jnp.float32)
    rot.advance()
    b = rot.acquire((N,), jnp.float32)
    # double-buffering: the second bank must not recycle the first bank's
    # live buffer
    assert a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
    assert rot.in_flight == 2
    rot.retire()                       # oldest bank (a) returns to the pool
    assert rot.in_flight == 1
    c = pool.acquire((N,), jnp.float32)
    assert c.unsafe_buffer_pointer() == a.unsafe_buffer_pointer()


def test_rotation_advance_auto_retires_when_full():
    pool = DeviceBufferPool(min_elems=1)
    rot = BufferRotation(pool, depth=2)
    rot.acquire((64,), jnp.float32)
    rot.advance()
    rot.acquire((64,), jnp.float32)
    assert rot.in_flight == 2
    rot.advance()          # rotation full: oldest bank retires automatically
    assert rot.in_flight == 1


def test_rotation_drain_releases_everything():
    pool = DeviceBufferPool(min_elems=1)
    rot = BufferRotation(pool, depth=3)
    rot.acquire((64,), jnp.float32)
    rot.advance()
    rot.acquire((64,), jnp.float32)
    rot.acquire((64,), jnp.float32)       # same active bank
    assert rot.in_flight == 3
    rot.drain()
    assert rot.in_flight == 0
    # all three buffers are reusable again
    hits_before = pool.stats.hits
    for _ in range(3):
        pool.acquire((64,), jnp.float32)
    assert pool.stats.hits == hits_before + 3


def test_rotation_depth_validation():
    with pytest.raises(ValueError):
        BufferRotation(DeviceBufferPool(), depth=1)


def test_rotation_generation_tag_rejects_stale_registrations():
    """A background staging task that outlives its replay (drain bumps the
    generation) must hand its buffer back to the pool, not park it in the
    next replay's banks."""
    pool = DeviceBufferPool(min_elems=1)
    rot = BufferRotation(pool, depth=2)
    handle = rot.handle()                  # minted in generation 0
    live = rot.acquire((64,), jnp.float32)
    rot.drain()                            # replay ends: generation 1
    stale = pool.acquire((64,), jnp.float32)
    handle.register(stale)                 # stale task lands late
    assert rot.in_flight == 0              # NOT parked in a bank
    again = pool.acquire((64,), jnp.float32)
    assert again.unsafe_buffer_pointer() == stale.unsafe_buffer_pointer()
    # a fresh handle follows the current generation and parks normally
    rot.handle().register(again)
    assert rot.in_flight == 1
