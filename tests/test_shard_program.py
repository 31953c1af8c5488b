"""Sharded region programs (repro.core.shard_program): halo-width
inference from DIA offsets (plus hypothesis property tests), degenerate
1-device decomposition == plain replay, wide-halo ghost-zone value
identity, overlap-aware per-device ledger aggregation arithmetic, sharded
pooling, and the real multi-device parity checks (subprocess — the APU
count must be in XLA_FLAGS before jax imports, and this process already
sees one device): the 2-APU cavity acceptance run, the remainder-row
padding case, and the schedule x halo-width x mesh x policy parity
matrix (``python tests/test_shard_program.py --matrix`` under 4 forced
devices)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # direct `python tests/... --matrix` run: no conftest
    # stub installed and the property tests aren't reached — inert deco's
    class _InertStrategy:
        def __call__(self, *a, **k):
            return self

        def __getattr__(self, name):
            return self

    st = _InertStrategy()

    def given(*_a, **_k):
        return lambda fn: fn

    def settings(*_a, **_k):
        return lambda fn: fn

from repro.cfd.dia import STENCIL_OFFSETS, compose_offsets
from repro.core.ledger import Ledger
from repro.core.pool import DeviceBufferPool
from repro.core.program import capture
from repro.core.regions import (DiscretePolicy, Executor, UnifiedPolicy,
                                region)
from repro.core.shard_program import (ShardExecutor, ShardedProgram,
                                      halo_width, shard_program)
from repro.launch.mesh import make_apu_mesh, parse_mesh_shape

GRID = (8, 8, 8)


def apu_mesh_1():
    return make_apu_mesh(1)


def make_field_program(ledger=None):
    """A small cavity-shaped program over 3-D fields: a pointwise region,
    a stencil region (declared DIA offsets + halo_args), and a reduction
    frozen as a constant."""
    kw = dict(ledger=ledger or Ledger("shard_test"))

    @region("scale", **kw)
    def scale(d, x):
        return d * x

    @region("stencil", stencil=STENCIL_OFFSETS, halo_args=("x",), **kw)
    def stencil(c, x):
        nz = x.shape[2]
        zlo = jnp.pad(x, ((0, 0), (0, 0), (1, 0)))[:, :, :nz]
        return c * x + zlo

    @region("dot", **kw)
    def dot(x, y):
        return jnp.sum(x * y)

    def step(run, d, x):
        a = run(scale, d, x)
        b = run(stencil, d, a)
        s = float(run(dot, b, b))              # frozen control-flow scalar
        return run(scale, s / (abs(s) + 1.0), b)

    d = jnp.linspace(1.0, 2.0, int(np.prod(GRID))).reshape(GRID)
    x = jnp.full(GRID, 0.3, jnp.float32)
    return capture(step, d, x, name="mini3d"), (d, x)


# ---------------------------------------------------------------------------
# Halo-width inference
# ---------------------------------------------------------------------------

def test_halo_width_from_dia_offsets():
    # one band per face direction: width 1 along every grid axis
    for axis in range(3):
        assert halo_width(STENCIL_OFFSETS, axis) == 1
    # composed 7-point stencils (e.g. the two DILU half-sweeps) reach 2
    composed = compose_offsets(STENCIL_OFFSETS, STENCIL_OFFSETS)
    assert halo_width(composed, 2) == 2
    # pointwise regions exchange nothing
    assert halo_width(None, 2) == 0
    assert halo_width((), 2) == 0
    # offsets on other axes don't bleed into the decomposed one
    assert halo_width(((0, -1), (0, 1)), 2) == 0


def test_solver_regions_declare_stencils():
    from repro.cfd.solvers import make_solver_regions
    R = make_solver_regions(Ledger("decl"))
    assert halo_width(R.amul.stencil, 2) == 1
    assert halo_width(R.precond.stencil, 2) == 2    # two half-sweeps
    assert R.dot.stencil is None                    # reductions: pointwise


# ---------------------------------------------------------------------------
# Property tests (hypothesis; skip when it isn't installed — conftest stub)
# ---------------------------------------------------------------------------

offsets_st = st.lists(st.tuples(st.integers(0, 2), st.integers(-3, 3)),
                      max_size=12).map(tuple)


@given(offsets_st)
@settings(deadline=None, max_examples=100)
def test_prop_halo_width_covers_every_declared_offset(offsets):
    """The inferred halo width is never narrower than any declared band:
    a decomposition exchanging ``halo_width`` ghost layers always covers
    the stencil's reach on that axis (and is exactly the max reach)."""
    for ax, d in offsets:
        assert halo_width(offsets, ax) >= abs(d)
    for ax in range(3):
        assert halo_width(offsets, ax) == max(
            (abs(d) for a, d in offsets if a == ax), default=0)


@given(offsets_st, offsets_st)
@settings(deadline=None, max_examples=100)
def test_prop_compose_offsets_monotone_under_composition(a, b):
    """compose_offsets is inflationary and subadditive: chaining two
    stencils never shrinks the reach of either (monotone), and never
    reaches further than the sum of the two (Minkowski bound)."""
    comp = compose_offsets(a, b)
    assert set(a) <= set(comp) and set(b) <= set(comp)
    for ax in range(3):
        wa, wb, wc = (halo_width(a, ax), halo_width(b, ax),
                      halo_width(comp, ax))
        assert wc >= max(wa, wb)       # monotone
        assert wc <= wa + wb           # subadditive


def _stencil1d(x):
    """width-1 reference stencil with the zero-Dirichlet global boundary:
    y[i] = x[i-1] + 2 x[i] + x[i+1]."""
    p = np.pad(x, 1)
    return p[:-2] + 2.0 * x + p[2:]


def _exchanged_steps(chunks, n_steps, ghost):
    """The chunked ghost-zone model of the sharded replay: ONE exchange of
    ``ghost``-wide halos, then ``n_steps`` stencil applications on the
    extended chunks, keeping the interior.  Valid while n_steps <= ghost
    (one layer of ghost validity is consumed per application).  Ghost
    cells past the global boundary hold the zero-Dirichlet value through
    every application, as the undecomposed boundary does."""
    assert n_steps <= ghost
    n = len(chunks)
    ext = []
    for i, c in enumerate(chunks):
        left = chunks[i - 1][-ghost:] if i > 0 else np.zeros(
            ghost, c.dtype)
        right = chunks[i + 1][:ghost] if i < n - 1 else np.zeros(
            ghost, c.dtype)
        ext.append(np.concatenate([left, c, right]))
    for _ in range(n_steps):
        ext = [_stencil1d(e) for e in ext]
        ext[0][:ghost] = 0.0
        ext[-1][len(ext[-1]) - ghost:] = 0.0
    return [e[ghost:len(e) - ghost] for e in ext]


@given(st.lists(st.floats(-4.0, 4.0, allow_nan=False, width=32),
                min_size=8, max_size=48),
       st.integers(1, 3), st.integers(2, 4))
@settings(deadline=None, max_examples=50)
def test_prop_wide_halo_replay_value_identical(vals, k, nchunks):
    """The wide-halo schedule's contract: one width-k exchange followed by
    k stencil applications is VALUE-IDENTICAL (bit-exact) to k separate
    width-1 exchanged steps — and both equal the undecomposed replay."""
    m = len(vals) // nchunks
    if m < k:                          # chunks must hold >= k ghost cells
        m = k
        nchunks = max(2, len(vals) // m)
        if len(vals) < 2 * m:
            return                     # domain too small for this k
    x = np.asarray(vals[:m * nchunks], np.float32)
    chunks = [x[i * m:(i + 1) * m] for i in range(nchunks)]

    wide = np.concatenate(_exchanged_steps(chunks, k, ghost=k))
    narrow = chunks
    for _ in range(k):                 # k width-1 exchanged steps
        narrow = _exchanged_steps(narrow, 1, ghost=1)
    narrow = np.concatenate(narrow)
    ref = x
    for _ in range(k):
        ref = _stencil1d(ref)

    np.testing.assert_array_equal(wide, narrow)
    np.testing.assert_array_equal(wide, ref)


# ---------------------------------------------------------------------------
# Degenerate 1-device mesh == plain replay
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_policy", [UnifiedPolicy, DiscretePolicy])
def test_one_device_mesh_equals_plain_replay(make_policy):
    prog, (d, x) = make_field_program()
    ref = prog.replay(Executor(make_policy()), d, x)
    sp = shard_program(prog, apu_mesh_1(), make_policy())
    out = sp.replay(d, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    rep = sp.coverage_report()
    assert rep["devices"] == 1
    # a single shard has no neighbor to exchange with: the halo region
    # still runs (and is accounted) but moves zero inter-APU bytes
    assert rep["exchange_bytes"] == 0
    assert "halo(stencil)" in sp.ledgers[0].regions


def test_sharded_program_quacks_like_an_executor():
    """prog.replay(sharded, ...) dispatches through the replay_program
    hook, so SimpleFoam.replay_steps & co. take a ShardedProgram as-is."""
    prog, (d, x) = make_field_program()
    sp = shard_program(prog, apu_mesh_1(), UnifiedPolicy())
    out_via_prog = prog.replay(sp, d, x)
    np.testing.assert_array_equal(np.asarray(out_via_prog),
                                  np.asarray(sp.replay(d, x)))


def test_sharding_rule():
    sp = shard_program(make_field_program()[0], apu_mesh_1(),
                       UnifiedPolicy())
    ex = sp.executor
    field = jnp.zeros(GRID)
    off = jnp.zeros((6,) + GRID)
    scalar = jnp.float32(1.0)
    assert ex.sharding_for(field).spec == jax.sharding.PartitionSpec(
        None, None, "apu")
    assert ex.sharding_for(off).spec == jax.sharding.PartitionSpec(
        None, None, None, "apu")
    assert ex.sharding_for(scalar).spec == jax.sharding.PartitionSpec()


def test_parse_mesh_shape_and_pad_grid():
    from repro.launch.scaling import pad_grid
    assert parse_mesh_shape("4") == (4,)
    assert parse_mesh_shape(4) == (4,)
    assert parse_mesh_shape("2x2") == (2, 2)
    assert parse_mesh_shape("2x2x2") == (2, 2, 2)
    # remainder-row padding: odd extents grow to the next mesh multiple
    assert pad_grid((8, 8, 9), (2,)) == (8, 8, 10)
    assert pad_grid((8, 9, 9), (2, 2)) == (8, 10, 10)
    assert pad_grid((8, 8, 8), (2, 4)) == (8, 8, 8)


def test_2d_mesh_sharding_rule_and_report():
    """Degenerate (1,1) 2-D mesh in-process: fields decompose over BOTH
    trailing dims, the replay matches the plain one, and the report
    carries the new schedule keys."""
    prog, (d, x) = make_field_program()
    ref = prog.replay(Executor(UnifiedPolicy()), d, x)
    mesh = make_apu_mesh((1, 1))
    sp = shard_program(prog, mesh, UnifiedPolicy())
    ex = sp.executor
    assert ex.sharding_for(jnp.zeros(GRID)).spec == \
        jax.sharding.PartitionSpec(None, "apu0", "apu1")
    assert ex.sharding_for(jnp.zeros((6,) + GRID)).spec == \
        jax.sharding.PartitionSpec(None, None, "apu0", "apu1")
    out = sp.replay(d, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    rep = sp.coverage_report()
    assert rep["mesh_shape"] == [1, 1]
    assert rep["schedule"] == "overlap"
    assert rep["halo_multiplier"] == 1
    assert "overlap_s" in rep and "overlap_s" in rep["per_device"][0]


@pytest.mark.parametrize("schedule,k", [("overlap", 2), ("sequential", 3),
                                        ("split", 1)])
def test_schedules_match_plain_replay_one_device(schedule, k):
    """Every exchange schedule x wide-halo combination reproduces the
    plain replay on a degenerate mesh, across chained steps (the wide-halo
    plan cycles through due and skipped exchanges)."""
    prog, (d, x) = make_field_program()
    ex = Executor(UnifiedPolicy())
    sp = shard_program(prog, apu_mesh_1(), UnifiedPolicy(),
                       halo_multiplier=k,
                       overlap=schedule != "sequential",
                       split_stencil=schedule == "split")
    ref, cur = x, x
    for _ in range(2 * k):             # full halo-plan cycle, twice
        ref = prog.replay(ex, d, ref)
        cur = sp.replay(d, cur)
    if schedule == "split":            # blend pass recompiles the region:
        scale = max(float(np.max(np.abs(np.asarray(ref)))), 1.0)
        np.testing.assert_allclose(np.asarray(cur), np.asarray(ref),
                                   atol=1e-5 * scale, rtol=0)
    else:
        np.testing.assert_array_equal(np.asarray(cur), np.asarray(ref))
    assert sp.coverage_report()["schedule"] == schedule


# ---------------------------------------------------------------------------
# Ledger aggregation arithmetic
# ---------------------------------------------------------------------------

def make_device_ledgers(n=4):
    """N per-device ledgers recording the 1/N-share convention for one
    stencil region + its halo row, with known numbers."""
    ledgers = [Ledger(f"apu{i}") for i in range(n)]
    for led in ledgers:
        led.record("Amul", device=True, offloaded=True,
                   compute_s=0.4 / n, staging_s=0.2 / n,
                   staging_bytes=4096 // n, elems=512 // n)
        led.record("halo(Amul)", device=True, offloaded=True,
                   compute_s=0.0, exchange_s=0.1 / n, exchange_bytes=256)
    return ledgers


def test_merged_ledger_reproduces_node_totals():
    ledgers = make_device_ledgers(4)
    node = Ledger.merged(ledgers)
    rep = node.coverage_report()
    assert rep["compute_s"] == pytest.approx(0.4)
    assert rep["staging_s"] == pytest.approx(0.2)
    assert rep["exchange_s"] == pytest.approx(0.1)
    assert rep["exchange_bytes"] == 4 * 256
    assert rep["total_s"] == pytest.approx(0.7)     # compute+staging+exchange
    assert rep["exchange_fraction"] == pytest.approx(0.1 / 0.7)
    assert rep["staging_fraction"] == pytest.approx(0.2 / 0.7)
    # per-row: exchange lands on the halo row, not the stencil row
    assert node.regions["Amul"].exchange_s == 0.0
    assert node.regions["halo(Amul)"].exchange_s == pytest.approx(0.1)
    assert node.regions["halo(Amul)"].total_s == pytest.approx(0.1)


def test_merged_ledger_excludes_overlapped_exchange_from_totals():
    """Overlap accounting invariant on fabricated per-device ledgers:
    total ~= compute + staging + exchange - overlap, and the exchange
    fraction is computed from the EXPOSED (un-hidden) exchange time."""
    n = 2
    ledgers = [Ledger(f"apu{i}") for i in range(n)]
    for led in ledgers:
        led.record("Amul", device=True, offloaded=True,
                   compute_s=0.4 / n, staging_s=0.1 / n)
        led.record("halo(Amul)", device=True, offloaded=True,
                   compute_s=0.0, exchange_s=0.2 / n, exchange_bytes=128,
                   overlap_s=0.15 / n)
    node = Ledger.merged(ledgers)
    rep = node.coverage_report()
    assert rep["compute_s"] == pytest.approx(0.4)
    assert rep["staging_s"] == pytest.approx(0.1)
    assert rep["exchange_s"] == pytest.approx(0.2)
    assert rep["overlap_s"] == pytest.approx(0.15)
    # the invariant this PR fixes: overlapped exchange is NOT double-counted
    assert rep["total_s"] == pytest.approx(0.4 + 0.1 + 0.2 - 0.15)
    # exposed exchange = exchange - overlap (halo rows have no staging)
    assert rep["exposed_exchange_s"] == pytest.approx(0.05)
    assert rep["exchange_fraction"] == pytest.approx(0.05 / rep["total_s"])
    # per-row: the halo row's own wall-clock contribution is its exposure
    assert node.regions["halo(Amul)"].total_s == pytest.approx(0.05)
    assert node.regions["halo(Amul)"].exposed_exchange_s == \
        pytest.approx(0.05)


def test_record_accepts_overlap_and_clamps_it():
    led = Ledger("x")
    # overlap can never exceed the hideable time (staging + exchange)
    led.record("h", device=True, compute_s=0.0, exchange_s=0.2,
               staging_s=0.1, overlap_s=9.0)
    assert led.regions["h"].overlap_s == pytest.approx(0.3)
    assert led.regions["h"].total_s == pytest.approx(0.0)
    led.reset_timings()
    assert led.regions["h"].overlap_s == 0.0


def test_record_accepts_exchange_and_resets_it():
    led = Ledger("x")
    led.record("r", device=True, compute_s=1.0, exchange_s=0.5,
               exchange_bytes=100)
    assert led.regions["r"].total_s == pytest.approx(1.5)
    led.reset_timings()
    assert led.regions["r"].exchange_s == 0.0
    assert led.regions["r"].exchange_bytes == 0


def test_same_named_regions_keep_distinct_rows():
    """Two distinct Region objects sharing a display name (registered in
    different app ledgers) must not merge into one per-device row — the
    Executor._row_name contract, upheld by ShardExecutor."""
    @region("Amul", ledger=Ledger("a"))
    def amul1(x):
        return x * 2.0

    @region("Amul", ledger=Ledger("b"))
    def amul2(x):
        return x + 1.0

    def step(run, x):
        return run(amul2, run(amul1, x))

    prog = capture(step, jnp.ones(GRID), name="dup")
    sp = shard_program(prog, apu_mesh_1(), UnifiedPolicy())
    sp.replay(jnp.ones(GRID))
    rows = sp.ledgers[0].regions
    assert "Amul" in rows and "Amul#2" in rows
    assert rows["Amul"].calls == 1 and rows["Amul#2"].calls == 1


def test_report_per_device_breakdown_sums_to_aggregate():
    prog, (d, x) = make_field_program()
    sp = shard_program(prog, apu_mesh_1(), UnifiedPolicy())
    sp.replay(d, x)
    rep = sp.coverage_report()
    assert len(rep["per_device"]) == rep["devices"] == 1
    per = rep["per_device"][0]
    for key in ("compute_s", "staging_s", "exchange_s"):
        assert per[key] == pytest.approx(rep[key], abs=1e-9), key
    assert per["exchange_s"] >= 0.0
    assert rep["mode"].startswith("unified+sharded")


# ---------------------------------------------------------------------------
# Batched replay over the mesh + sharded pooling
# ---------------------------------------------------------------------------

def test_replay_steps_mesh_kwarg_matches_plain_replay():
    """SimpleFoam.replay_steps(mesh=...) rebinds a plain Executor into the
    decomposition (convenience path; reports need an explicit
    ShardExecutor) and rejects executors it cannot rebind."""
    from repro.cfd.grid import Grid
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    from repro.core.program import AsyncExecutor
    cfg = SimpleConfig(grid=Grid((6, 6, 6)), nu=0.1, inner_max=3)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)
    prog = app.capture_step(st)
    s_plain, _ = app.replay_steps(prog, st, 1, Executor(UnifiedPolicy()))
    mesh = apu_mesh_1()
    s_mesh, _ = app.replay_steps(prog, st, 1, Executor(UnifiedPolicy()),
                                 mesh=mesh)
    for a, b in zip((s_plain.u, s_plain.v, s_plain.w, s_plain.p),
                    (s_mesh.u, s_mesh.v, s_mesh.w, s_mesh.p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="cannot rebind"):
        app.replay_steps(prog, st, 1, AsyncExecutor(UnifiedPolicy()),
                         mesh=mesh)


def test_sharded_replay_batch_matches_sequential():
    prog, (d, x) = make_field_program()
    sp = shard_program(prog, apu_mesh_1(), UnifiedPolicy(), shard_dim=0)
    B = 2
    ds = jnp.stack([d] * B)
    xs = jnp.stack([x + 0.01 * i for i in range(B)])
    batched = sp.replay_batch(ds, xs)
    ex = Executor(UnifiedPolicy())
    seq = jnp.stack([prog.replay(ex, ds[i], xs[i]) for i in range(B)])
    np.testing.assert_allclose(np.asarray(batched), np.asarray(seq),
                               rtol=1e-6, atol=1e-6)
    assert "mini3d[batch]" in sp.ledgers[0].regions


def test_device_pool_recycles_sharded_buffers():
    mesh = apu_mesh_1()
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, None, "apu"))
    pool = DeviceBufferPool(min_elems=1)
    a = pool.acquire(GRID, jnp.float32, sharding=sh)
    assert a.sharding == sh
    pool.release(a)
    b = pool.acquire(GRID, jnp.float32, sharding=sh)
    assert pool.stats.hits == 1
    # plain acquires don't steal from the sharded bucket
    pool.release(b)
    c = pool.acquire(GRID, jnp.float32)
    assert pool.stats.hits == 1 and pool.stats.misses == 2
    assert c is not b


# ---------------------------------------------------------------------------
# Real multi-device parity (subprocess: needs its own XLA_FLAGS)
# ---------------------------------------------------------------------------

def test_two_apu_cavity_parity_subprocess(tmp_path):
    """The acceptance-criterion scenario at test scale: the captured
    SIMPLE step replayed on 1 vs 2 simulated APUs agrees within the
    docs/DESIGN.md §2 tolerance, and the aggregated report splits
    compute / staging / exchange per device."""
    out = tmp_path / "apu2.json"
    cmd = [sys.executable, "-m", "repro.launch.scaling", "--apus", "2",
           "--steps", "1", "--grid", "8,8,8", "--inner-max", "4",
           "--out", str(out)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env={**os.environ, "XLA_FLAGS": ""})
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["parity_ok"], rec
    assert rec["parity_max_abs_err"] <= rec["parity_tol"]
    rep = rec["report"]
    assert rep["devices"] == 2
    assert len(rep["per_device"]) == 2
    assert rep["exchange_s"] > 0.0
    assert rep["exchange_bytes"] > 0
    # 1/N recording convention: each APU ledger carries half of the node
    # aggregate (both sides derive from the same measured wall intervals,
    # so this checks the share arithmetic, not runtime load balance)
    a, b = rep["per_device"]
    assert a["compute_s"] + b["compute_s"] == pytest.approx(
        rep["compute_s"])
    assert a["compute_s"] == pytest.approx(rep["compute_s"] / 2)
    assert a["exchange_bytes"] + b["exchange_bytes"] == \
        rep["exchange_bytes"]
    # halo-exchange rows for the stencil regions are explicit
    assert any(n.startswith("halo(Amul)") for n in rec["halo_rows"])
    assert any("precondition" in n for n in rec["halo_rows"])


def test_odd_grid_remainder_padding_subprocess(tmp_path):
    """Production grids rarely divide evenly: an odd z-extent is padded up
    to the next mesh multiple (both replays run the padded grid, so parity
    stays meaningful) instead of silently replicating or refusing."""
    out = tmp_path / "odd.json"
    cmd = [sys.executable, "-m", "repro.launch.scaling", "--apus", "2",
           "--steps", "1", "--grid", "8,8,9", "--inner-max", "3",
           "--out", str(out)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env={**os.environ, "XLA_FLAGS": ""})
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["grid_requested"] == [8, 8, 9]
    assert rec["grid"] == [8, 8, 10]
    assert rec["grid_padded"] is True
    assert rec["parity_ok"], rec
    assert rec["report"]["exchange_bytes"] > 0


# ---------------------------------------------------------------------------
# Parity matrix: schedule x halo-width x mesh x policy, vs unsharded replay
# (one subprocess under 4 forced devices runs _matrix_main below)
# ---------------------------------------------------------------------------

#: covering design over the matrix axes — every schedule, both halo
#: widths, and both mesh ranks appear, each cell under all four policies
MATRIX_COMBOS = (
    ("overlap", 1, (4,)),
    ("sequential", 1, (4,)),
    ("overlap", 2, (4,)),
    ("sequential", 2, (2, 2)),
    ("overlap", 1, (2, 2)),
    ("split", 1, (4,)),
    ("split", 2, (2, 2)),
)
MATRIX_POLICIES = ("unified", "discrete", "adaptive", "host")


def _matrix_main() -> None:
    """Runs inside the subprocess (4 forced host devices): every
    MATRIX_COMBOS cell under every placement policy, two chained steps,
    compared against the same policy's unsharded replay — bit-exact for
    the exchange schedules (the roll-roundtrip is a value identity and
    partitioned elementwise compute is bitwise deterministic), DESIGN §2
    tolerance for the split schedule (the boundary blend is a separate
    compilation)."""
    from repro.core.regions import make_policy
    assert jax.device_count() >= 4, jax.devices()
    steps = 2
    prog, (d, x) = make_field_program()
    failures = []
    for policy_name in MATRIX_POLICIES:
        refs, cur = [], x
        ref_ex = Executor(make_policy(policy_name))
        for _ in range(steps):
            cur = prog.replay(ref_ex, d, cur)
            refs.append(np.asarray(cur))
        for schedule, k, mesh_shape in MATRIX_COMBOS:
            mesh = make_apu_mesh(mesh_shape)
            sp = shard_program(prog, mesh, make_policy(policy_name),
                               halo_multiplier=k,
                               overlap=schedule != "sequential",
                               split_stencil=schedule == "split")
            cur = x
            for s in range(steps):
                cur = sp.replay(d, cur)
                got = np.asarray(cur)
                tag = (f"{policy_name}/{schedule}/k={k}/"
                       f"mesh={'x'.join(map(str, mesh_shape))}/step{s}")
                err = float(np.max(np.abs(got - refs[s])))
                if schedule == "split":
                    tol = 1e-5 * max(float(np.max(np.abs(refs[s]))), 1.0)
                    ok = err <= tol
                else:
                    ok = np.array_equal(got, refs[s])
                if not ok:
                    failures.append(f"{tag} max_err={err:.3e}")
                else:
                    print(f"ok {tag} max_err={err:.3e}")
            rep = sp.coverage_report()
            if rep["mesh_shape"] != list(mesh_shape):
                failures.append(f"{tag} bad mesh_shape {rep['mesh_shape']}")
            # adaptive gathers small problems to the host and the offload
            # policy keeps assembly there — no decomposed compute, so no
            # exchange is CORRECT for them at this size; the guarantee
            # holds where device-sharded compute is guaranteed
            if policy_name in ("unified", "discrete"):
                if rep["exchange_bytes"] <= 0:
                    failures.append(f"{tag} no exchange bytes")
                if schedule == "overlap" and rep["overlap_s"] <= 0.0:
                    failures.append(f"{tag} no overlap recorded")
    if failures:
        print("MATRIX FAILURES:\n" + "\n".join(failures))
        raise SystemExit(1)
    print("MATRIX OK")


def test_parity_matrix_subprocess():
    """The satellite parity matrix: overlapped vs sequential vs split,
    width-1 vs wide-halo, 1-D vs 2-D mesh, under all four placement
    policies, against the unsharded replay (subprocess — needs 4 forced
    devices in XLA_FLAGS before jax imports)."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               p for p in (src, os.environ.get("PYTHONPATH")) if p)}
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--matrix"],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-2000:])
    assert "MATRIX OK" in r.stdout


if __name__ == "__main__":
    if "--matrix" in sys.argv:
        _matrix_main()
