"""Region implementation variants (the declare-variant Selector axis,
repro.core.regions): registration mechanics, selector resolution with the
base-function fallback, parity of every registered variant of every region
against its ref under all four policies (docs/DESIGN.md §2 tolerances),
AutotuneSelector calibration determinism + ledger persistence, variant
re-resolution on captured-program replay (sync, async, batched), the
kernel-package ref contract, and the 2-APU sharded acceptance scenario
(subprocess)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import fvm
from repro.cfd.fields import make_field_ops
from repro.cfd.grid import Grid
from repro.cfd.precond import rb_dilu_factor
from repro.cfd.solvers import make_solver_regions
from repro.core.ledger import Ledger
from repro.core.program import AsyncExecutor, capture
from repro.core.regions import (AdaptivePolicy, AutotuneSelector,
                                DiscretePolicy, Executor, HostPolicy,
                                StaticSelector, TargetSelector,
                                UnifiedPolicy, region, size_bucket)

GRID = (8, 6, 10)

#: docs/DESIGN.md §2 variant tolerance: variant-vs-ref agreement bound for
#: one region application (the Pallas kernel parity sweeps' bound)
VTOL = dict(rtol=3e-4, atol=1e-4)

ALL_POLICIES = [UnifiedPolicy, HostPolicy, DiscretePolicy,
                lambda **kw: AdaptivePolicy(cutoff=64, **kw)]


def scalars(*values):
    return tuple(jnp.float32(v) for v in values)


def solver_fixture():
    """Every variant-carrying region of the CFD stack with example args:
    [(region, make_args())] over a real assembled system."""
    g = Grid(GRID)
    A, _ = fvm.laplacian(g, 1.0)
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.rand(*GRID).astype(np.float32))
    y = jnp.asarray(rng.rand(*GRID).astype(np.float32))
    z = jnp.asarray(rng.rand(*GRID).astype(np.float32))
    R = make_solver_regions(Ledger("vfix"))
    ops = make_field_ops(Ledger("vfix_ops"))
    return [
        (R.amul, (A.diag, A.off, x)),
        (R.precond, (P.rdiag, P.red, A.off, x)),
        # Krylov scalars as the solver passes them: 0-d float32 arrays
        (R.saxpy, scalars(0.5, 0.7, 2.0) + (x, y)),
        (R.saxpy, scalars(0.0, 0.7, 2.0) + (x, y)),     # rho broke down
        (R.update_x, (x,) + scalars(0.3, 1.5) + (y,) + scalars(-0.2, 0.5)
         + (z,)),
        (ops.axpy, (1.5, x, y)),
        (ops.xpay, (-0.5, x, y)),
        (ops.axpbypz, (0.25, x, -1.5, y, z)),
        (ops.fmul, (x, y)),
    ]


# ---------------------------------------------------------------------------
# Region.variant mechanics
# ---------------------------------------------------------------------------

def test_every_region_has_ref_and_fallback_resolution():
    for r, _ in solver_fixture():
        assert "ref" in r.variants
        assert r.resolve("no-such-impl") == "ref"
        assert r.impl_fn("ref") is r.fn


def test_variant_registration_and_executable_cache():
    ldg = Ledger("t")

    @region("f", ledger=ldg)
    def f(x):
        return x + 1.0

    assert f.variants == ("ref",)

    @f.variant("double")
    def _g(x):
        return x + 2.0

    assert f.variants == ("ref", "double")
    np.testing.assert_allclose(
        np.asarray(f.executable("default", "double")(jnp.zeros(8))), 2.0)
    np.testing.assert_allclose(
        np.asarray(f.executable("default")(jnp.zeros(8))), 1.0)
    with pytest.raises(KeyError, match="no variant"):
        f.impl_fn("nope")
    # re-registration drops the stale compilation
    f.variant("double", lambda x: x + 3.0)
    np.testing.assert_allclose(
        np.asarray(f.executable("default", "double")(jnp.zeros(8))), 3.0)
    # re-registering "ref" replaces the BASE function everywhere: jitted
    # executables and the raw fn (the fused as_fn path) must agree
    f.variant("ref", lambda x: x + 10.0)
    np.testing.assert_allclose(
        np.asarray(f.executable("default")(jnp.zeros(8))), 10.0)
    np.testing.assert_allclose(
        np.asarray(f.jitted_variant("ref")(jnp.zeros(8))), 10.0)
    np.testing.assert_allclose(np.asarray(f.fn(jnp.zeros(8))), 10.0)


def test_unknown_selector_name_falls_back_on_every_path():
    """A custom Selector may return an unregistered name: every executor
    path (incl. jitted_variant and the fused composite) must fall back to
    ref, and the ledger must record what actually ran."""
    ldg = Ledger("t")

    @region("f", ledger=ldg)
    def f(x):
        return x * 2.0

    ex = Executor(UnifiedPolicy(selector=StaticSelector("cuda")), ldg)
    np.testing.assert_allclose(np.asarray(ex.run(f, jnp.ones(8))), 2.0)
    assert ldg.coverage_report()["impl_counts"] == {"ref": 1}
    np.testing.assert_allclose(
        np.asarray(f.jitted_variant("cuda")(jnp.ones(8))), 2.0)

    def step(run, x):
        return run(f, x)

    prog = capture(step, jnp.ones(8), name="fb")
    out = prog.replay_batch(jnp.ones((2, 8)),
                            selector=StaticSelector("cuda"))
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_target_selector_prefers_device_kernel_and_host_path():
    ldg = Ledger("t")

    @region("f", ledger=ldg)
    def f(x):
        return x * 1.0

    f.variant("pallas", lambda x: x * 2.0)
    f.variant("host", lambda x: x * 3.0)
    sel = TargetSelector()
    assert sel.select(f, "default", (), {}) == "pallas"
    assert sel.select(f, "device", (), {}) == "pallas"
    assert sel.select(f, "host", (), {}) == "host"

    @region("g", ledger=ldg)         # no variants: everything falls back
    def g(x):
        return x

    assert sel.select(g, "device", (), {}) == "ref"
    assert sel.select(g, "host", (), {}) == "ref"


# ---------------------------------------------------------------------------
# Parity: every registered variant == ref under every policy (§2 tolerance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_policy", ALL_POLICIES)
def test_every_variant_matches_ref_under_every_policy(make_policy):
    for r, args in solver_fixture():
        ref_out = np.asarray(
            Executor(make_policy(selector=StaticSelector("ref")),
                     Ledger("ref")).run(r, *args))
        for name in r.variants:
            if name == "ref":
                continue
            ex = Executor(make_policy(selector=StaticSelector(name)),
                          Ledger(name))
            out = np.asarray(ex.run(r, *args))
            np.testing.assert_allclose(
                out, ref_out, **VTOL,
                err_msg=f"{r.name}:{name} vs ref under "
                        f"{ex.policy.name}")
            rep = ex.report()
            want = name if name in r.variants else "ref"
            assert rep["impl_counts"] == {want: 1}


def test_rwkv6_scan_variants_match_ref_with_nonzero_state():
    from repro.models.rwkv6 import RWKV6_SCAN
    B, T, H, hd = 2, 32, 2, 8
    rng = np.random.RandomState(3)
    r, k, v = [jnp.asarray(rng.randn(B, T, H, hd).astype(np.float32)) * 0.5
               for _ in range(3)]
    logw = -jnp.asarray(rng.rand(B, T, H, hd).astype(np.float32)) - 0.01
    u = jnp.asarray(rng.randn(H, hd).astype(np.float32)) * 0.3
    S0 = jnp.asarray(rng.randn(B, H, hd, hd).astype(np.float32)) * 0.2
    assert set(RWKV6_SCAN.variants) >= {"ref", "chunked", "pallas"}
    ro, rs = RWKV6_SCAN.impl_fn("ref")(r, k, v, logw, u, S0)
    for name in ("chunked", "pallas"):
        o, s = RWKV6_SCAN.jitted_variant(name)(r, k, v, logw, u, S0)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                                   rtol=3e-4, atol=3e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


def test_rwkv_train_impl_dispatch_matches_default():
    from repro.configs.base import ModelConfig
    from repro.models import rwkv6 as R
    from repro.models.params import init_params

    cfg = ModelConfig(name="t", family="ssm", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab=64,
                      layer_cycle=("rwkv",))

    class Ctx:
        rwkv_chunk = 8

        @staticmethod
        def shd(x, *_):
            return x

    rng = np.random.RandomState(0)
    p = init_params(jax.random.PRNGKey(0), R.rwkv_specs(cfg))
    x = jnp.asarray(rng.randn(2, 16, 16).astype(np.float32))
    y0, s0 = R.rwkv_train(p, x, cfg, ctx=Ctx, chunk=8)
    for impl in ("ref", "chunked", "pallas"):
        y, s = R.rwkv_train(p, x, cfg, ctx=Ctx, chunk=8, impl=impl)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   rtol=5e-4, atol=5e-4, err_msg=impl)
        np.testing.assert_allclose(np.asarray(s["S"]), np.asarray(s0["S"]),
                                   rtol=5e-4, atol=5e-4, err_msg=impl)


# ---------------------------------------------------------------------------
# AutotuneSelector
# ---------------------------------------------------------------------------

def test_autotune_calibration_is_deterministic_and_persisted():
    ldg = Ledger("t")

    @region("tuned", ledger=ldg)
    def tuned(x):
        return x * 2.0 + 1.0

    @tuned.variant("slow")
    def _slow(x):
        y = x
        for _ in range(50):              # deterministically slower
            y = jnp.sin(y) * 1.0001
        return y * 0.0 + x * 2.0 + 1.0

    sizes = (256, 4096)
    winners = {}
    for trial in range(2):
        sel = AutotuneSelector()
        winners[trial] = sel.calibrate(
            tuned, lambda n: (jnp.ones(n),), sizes=sizes, reps=3)
    # calibration on fixed sizes picks a stable winner
    assert winners[0] == winners[1]
    assert set(winners[0].values()) == {"ref"}
    rep = ldg.coverage_report()
    cells = rep["calibrated_variants"]["tuned"]
    assert cells == {f"default@2^{size_bucket(n)}": "ref" for n in sizes}
    assert rep["variant_wins"] == {"ref": len(sizes)}
    # selection honors the calibrated cell (and nearest-bucket fallback)
    sel = AutotuneSelector()
    sel.calibrate(tuned, lambda n: (jnp.ones(n),), sizes=sizes, reps=2)
    assert sel.select(tuned, "default", (jnp.ones(4096),), {}) == "ref"
    assert sel.select(tuned, "default", (jnp.ones(1 << 20),), {}) == "ref"


def test_autotune_mirrors_winner_into_foreign_ledger():
    ldg = Ledger("own")

    @region("m", ledger=ldg)
    def m(x):
        return x + 1.0

    foreign = Ledger("foreign")
    sel = AutotuneSelector()
    sel.calibrate(m, lambda n: (jnp.ones(n),), sizes=(256,), reps=1,
                  ledger=foreign)
    assert "m" in foreign.coverage_report()["calibrated_variants"]


def test_size_bucket_model():
    assert size_bucket(1) == 1
    assert size_bucket(255) == 8
    assert size_bucket(256) == 9          # [2^8, 2^9)
    assert size_bucket(511) == 9
    assert size_bucket(512) == 10


# ---------------------------------------------------------------------------
# Captured programs re-resolve variants at replay
# ---------------------------------------------------------------------------

def replay_fixture():
    ldg = Ledger("prog")

    @region("work", ledger=ldg)
    def work(x):
        return x * 2.0 + 1.0

    @work.variant("pallas")
    def _w(x):
        return (x + 0.0) * 2.0 + 1.0

    @region("tail", ledger=ldg)          # no variants: fallback territory
    def tail(x):
        return x - 0.5

    def step(run, x):
        return run(tail, run(work, x))

    x = jnp.linspace(0.0, 1.0, 1 << 14)
    return capture(step, x, name="vprog"), x


def test_one_trace_replays_under_any_selector_sync_async():
    prog, x = replay_fixture()
    outs = {}
    for sel in ("ref", "pallas"):
        for make_ex in (lambda s: Executor(UnifiedPolicy(
                            selector=StaticSelector(s))),
                        lambda s: AsyncExecutor(DiscretePolicy(
                            selector=StaticSelector(s)))):
            ex = make_ex(sel)
            out = np.asarray(prog.replay(ex, x))
            outs.setdefault(sel, out)
            np.testing.assert_allclose(out, outs[sel], rtol=1e-6, atol=1e-7)
            counts = ex.report()["impl_counts"]
            # the variant-carrying op follows the selector; the plain op
            # falls back to ref — proof the trace re-resolves per replay
            if sel == "pallas":
                assert counts == {"pallas": 1, "ref": 1}, counts
            else:
                assert counts == {"ref": 2}, counts
    np.testing.assert_allclose(outs["pallas"], outs["ref"],
                               rtol=1e-6, atol=1e-7)


def test_replay_batch_accepts_selector():
    prog, x = replay_fixture()
    xs = jnp.stack([x, x + 0.25])
    base = prog.replay_batch(xs)
    for sel in (StaticSelector("pallas"), None):
        out = prog.replay_batch(xs, selector=sel)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Kernel-package contract
# ---------------------------------------------------------------------------

def test_kernel_packages_all_register_ref():
    from repro.kernels import (PACKAGES, REQUIRED_VARIANT,
                               check_ref_variants, variant_tables)
    tables = variant_tables()
    assert set(tables) == set(PACKAGES)
    for pkg, ops in tables.items():
        for op, table in ops.items():
            assert REQUIRED_VARIANT in table, f"{pkg}.{op}"
            assert "pallas" in table, f"{pkg}.{op}"
    assert check_ref_variants() == {pkg: len(ops)
                                    for pkg, ops in tables.items()}


# ---------------------------------------------------------------------------
# Acceptance scenario: one captured SIMPLE step under every selector,
# sync + async (the 2-APU sharded leg runs in a subprocess below)
# ---------------------------------------------------------------------------

def cavity_fixture():
    from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
    cfg = SimpleConfig(grid=Grid((8, 8, 8)), nu=0.1, inner_max=5)
    app = SimpleFoam(cfg)
    st = init_state(cfg)
    st, _, _ = app.run_steps(st, 1)
    return app, st, app.capture_step(st)


def _fields(s):
    return [np.asarray(f) for f in (s.u, s.v, s.w, s.p)]


def test_cavity_step_replays_under_every_selector():
    app, st, prog = cavity_fixture()
    # a calibrated AutotuneSelector over the two solver hot-spot regions
    auto = AutotuneSelector()
    g = app.cfg.grid
    from repro.cfd import fvm
    A, _ = fvm.laplacian(g, 1.0)
    x = jnp.ones(g.shape, jnp.float32)
    auto.calibrate(app.solver_regions.amul,
                   lambda n: (A.diag, A.off, x), sizes=(g.n,), reps=2)
    selectors = {"ref": StaticSelector("ref"),
                 "pallas": StaticSelector("pallas"),
                 "autotuned": auto}
    outs, counts = {}, {}
    for name, sel in selectors.items():
        sync = Executor(UnifiedPolicy(selector=sel))
        s_sync, _ = app.replay_steps(prog, st, 1, sync)
        asyn = AsyncExecutor(DiscretePolicy(selector=sel))
        s_asyn, _ = app.replay_steps(prog, st, 1, asyn)
        outs[name] = _fields(s_sync)
        counts[name] = sync.report()["impl_counts"]
        scale = max(np.max(np.abs(f)) for f in outs[name])
        tol = 1e-5 * max(1.0, scale)              # DESIGN §2 bound
        for a, b in zip(outs[name], _fields(s_asyn)):
            if name == "autotuned":
                # sync routes "default", async discrete routes "device":
                # calibrated cells differ per target, so the two replays
                # may legitimately run different (parity-bounded) variants
                np.testing.assert_allclose(a, b, atol=tol, rtol=0,
                                           err_msg=name)
            else:
                # a static selector resolves identically on both
                # executors: same executables, bit-for-bit agreement
                np.testing.assert_array_equal(a, b, err_msg=name)
    # DESIGN §2 tolerance across selectors on the whole replayed step
    scale = max(np.max(np.abs(f)) for f in outs["ref"])
    tol = 1e-5 * max(1.0, scale)
    for name in ("pallas", "autotuned"):
        for a, b in zip(outs["ref"], outs[name]):
            np.testing.assert_allclose(a, b, atol=tol, rtol=0,
                                       err_msg=name)
    # impl_counts prove which variant ran where
    assert set(counts["ref"]) == {"ref"}
    assert counts["pallas"]["pallas"] > 0      # kernels engaged ...
    assert counts["pallas"]["ref"] > 0         # ... with ref fallback
    assert counts["autotuned"]["ref"] > 0      # uncalibrated regions: ref
    total = sum(counts["ref"].values())
    assert all(sum(c.values()) == total for c in counts.values())


def test_two_apu_sharded_replay_under_pallas_variant(tmp_path):
    """The sharded leg of the acceptance criterion: the SAME captured step
    replayed on 2 simulated APUs under StaticSelector('pallas') keeps §2
    parity with its single-device replay, and the aggregated node report's
    impl_counts prove the kernels ran decomposed."""
    out = tmp_path / "apu2_pallas.json"
    cmd = [sys.executable, "-m", "repro.launch.scaling", "--apus", "2",
           "--steps", "1", "--grid", "8,8,8", "--inner-max", "3",
           "--variant", "pallas", "--out", str(out)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env={**os.environ, "XLA_FLAGS": ""})
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["parity_ok"], rec
    assert rec["variant"] == "pallas"
    assert rec["impl_counts"].get("pallas", 0) > 0
    assert rec["impl_counts"].get("ref", 0) > 0     # fallback regions
    assert rec["report"]["devices"] == 2
