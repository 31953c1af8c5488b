"""repro.core.regions: Region + ExecutionPolicy API.

Covers the unified/discrete/host policy parity on a cavity time-step, the
adaptive (TARGET_CUT_OFF-inside-an-executor) policy's ledger accounting,
the uniform return contract, region-name uniquification, sizing, placement
hints, calibration recording, and the retired-shim import gate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd.grid import Grid
from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
from repro.core.ledger import Ledger
from repro.core.regions import (AdaptivePolicy, DiscretePolicy, Executor,
                                HostPolicy, MigrationStager, Region,
                                UnifiedPolicy, as_region, default_size,
                                make_policy, region)
from repro.core.umem import MemSpace, preferred_host_space, space_of


# ---------------------------------------------------------------------------
# policy parity (the paper's "same source, three platforms" claim)
# ---------------------------------------------------------------------------

def test_policy_parity_cavity_time_step():
    """unified / discrete / host policies must produce numerically identical
    cavity time_step results on an 8^3 grid."""
    cfg = SimpleConfig(grid=Grid((8, 8, 8)), nu=0.1, inner_max=20)
    outs = {}
    for name, policy in (("unified", UnifiedPolicy()),
                         ("discrete", DiscretePolicy()),
                         ("host", HostPolicy())):
        app = SimpleFoam(cfg, executor=Executor(policy))
        st, _ = app.time_step(init_state(cfg))
        outs[name] = st
    for name in ("discrete", "host"):
        for f in ("u", "v", "w", "p"):
            np.testing.assert_allclose(
                np.asarray(getattr(outs["unified"], f)),
                np.asarray(getattr(outs[name], f)),
                rtol=1e-5, atol=1e-6,
                err_msg=f"{name} diverges from unified on {f}")


def test_return_contract_is_jax_arrays():
    """One return contract across ALL policies: jax Arrays, never numpy
    (the old DiscreteExecutor leaked numpy, silently changing types)."""
    ldg = Ledger("t")

    @region("work", ledger=ldg)
    def work(x):
        return x * 2.0

    x = jnp.ones(8192)
    for mode in ("unified", "discrete", "host", "adaptive"):
        ex = Executor(make_policy(mode), Ledger(mode))
        out = ex.run(work, x)
        assert isinstance(out, jax.Array), f"{mode} broke the return contract"
        np.testing.assert_allclose(np.asarray(out), 2.0)


def test_discrete_staged_results_survive_pool_reuse():
    """A staged-out result must not alias a pooled host page: the next
    region's stage_out would overwrite it (zero-copy device_put on CPU)."""
    ldg = Ledger("t")

    @region("plus", ledger=ldg)
    def plus(x):
        return x + 1.0

    @region("zero", ledger=ldg)
    def zero(x):
        return x * 0.0

    ex = Executor(DiscretePolicy(), ldg)
    x = jnp.ones(6000)                   # above POOL_MIN_ELEMS=5120
    a = ex.run(plus, x)
    b = ex.run(zero, x)                  # same size class: pool would reuse
    np.testing.assert_allclose(np.asarray(a), 2.0)
    np.testing.assert_allclose(np.asarray(b), 0.0)


def test_discrete_device_pool_actually_reuses():
    """Staged-in device buffers must recycle through the DeviceBufferPool:
    release and acquire have to agree on the key even on backends whose
    default memory kind isn't named 'device' (CPU: unpinned_host)."""
    ldg = Ledger("t")

    @region("work", ledger=ldg)
    def work(x):
        return x + 1.0

    ex = Executor(DiscretePolicy(), ldg)
    pool = ex.policy.stager.device_pool
    for _ in range(4):
        ex.run(work, jnp.ones(8192))
    assert pool.stats.hits > 0                       # real reuse
    assert all(len(v) <= 2 for v in pool._free.values())  # no leak


def test_discrete_policy_stages_and_accounts():
    ldg = Ledger("t")

    @region("big", ledger=ldg)
    def big(x):
        return x + 1.0

    ex = Executor(DiscretePolicy(), ldg)
    x = jnp.ones(1 << 16)
    ex.run(big, x)
    rep = ex.report()
    assert rep["staging_s"] > 0
    r = ldg.regions["big"]
    assert r.staging_bytes >= 2 * x.nbytes          # operands in + results out
    # pooled staging actually engaged
    stager = ex.policy.stager
    assert isinstance(stager, MigrationStager)
    assert stager.host_pool.stats.hits + stager.host_pool.stats.misses > 0


def test_host_pool_recycles_when_results_die():
    """Pooled host staging pages must return to the pool once the staged
    result array is dropped (Umpire model), giving real reuse even on
    backends where the host wrap is zero-copy."""
    import gc
    ldg = Ledger("t")

    @region("work", ledger=ldg)
    def work(x):
        return x + 1.0

    ex = Executor(DiscretePolicy(), ldg)
    pool = ex.policy.stager.host_pool
    for _ in range(4):
        out = ex.run(work, jnp.ones(1 << 16))
        del out                          # app frees its host memory
        gc.collect()
    assert pool.stats.hits > 0           # later calls reuse released pages


# ---------------------------------------------------------------------------
# adaptive routing inside an executor
# ---------------------------------------------------------------------------

def test_adaptive_routing_lands_in_coverage_report():
    ldg = Ledger("t")

    @region("saxpy", ledger=ldg)
    def saxpy(x):
        return x * 3.0

    ex = Executor(AdaptivePolicy(cutoff=100), ldg)
    ex.run(saxpy, jnp.ones(10))          # below cutoff -> host
    ex.run(saxpy, jnp.ones(1000))        # above cutoff -> device
    rep = ex.report()
    assert rep["host_calls"] == 1 and rep["device_calls"] == 1
    assert 0 < rep["offload_elem_fraction"] < 1
    r = ldg.regions["saxpy"]
    assert r.host_elems == 10 and r.device_elems == 1000


def test_adaptive_policy_drives_region_program():
    """AdaptivePolicy must be drivable by the same executor machinery as
    the static modes — the composition the old TargetDispatch split made
    impossible."""
    cfg = SimpleConfig(grid=Grid((6, 6, 6)), nu=0.1, inner_max=15)
    app_ref = SimpleFoam(cfg, executor=Executor(UnifiedPolicy()))
    app_ad = SimpleFoam(cfg, executor=Executor(AdaptivePolicy(cutoff=64)))
    st_ref, _ = app_ref.time_step(init_state(cfg))
    st_ad, _ = app_ad.time_step(init_state(cfg))
    np.testing.assert_allclose(np.asarray(st_ref.u), np.asarray(st_ad.u),
                               rtol=1e-5, atol=1e-6)
    rep = app_ad.ex.report()
    assert rep["host_calls"] + rep["device_calls"] > 0
    # 6^3=216 cells > 64 cutoff: field regions route to device, scalar-ish
    # reductions still count somewhere — decisions are all in one report
    assert rep["device_calls"] > 0


def test_mixed_routing_splits_device_fraction():
    """One region routed both ways must attribute compute per side: a single
    device call must not claim the row's host time as device coverage."""
    ldg = Ledger("t")
    ldg.record("r", device=False, compute_s=9.0, elems=10)
    ldg.record("r", device=True, compute_s=1.0, elems=1000)
    rep = ldg.coverage_report()
    assert rep["device_compute_s"] == pytest.approx(1.0)
    assert rep["device_fraction"] == pytest.approx(0.1)
    r = ldg.regions["r"]
    assert r.host_compute_s == pytest.approx(9.0)
    assert r.device_compute_s == pytest.approx(1.0)


def test_tree_place_min_bytes_keeps_python_scalars():
    from repro.core.umem import tree_place
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    tree = {"len": 7, "kv": jnp.ones(8192)}
    out = tree_place(tree, host, min_bytes=1024)
    assert out["len"] == 7 and not isinstance(out["len"], jax.Array)
    assert space_of(out["kv"]) == host.kind


def test_calibrate_records_cutoff_in_ledger():
    ldg = Ledger("t")

    @region("kern", ledger=ldg)
    def kern(x):
        return x * 2.0 + 1.0

    pol = AdaptivePolicy()
    cut = pol.calibrate(kern, lambda n: (jnp.ones(n),),
                        sizes=(256, 4096), reps=2, ledger=ldg)
    assert pol.cutoff == cut
    assert ldg.regions["kern"].cutoff == cut
    assert ldg.coverage_report()["cutoffs"] == {"kern": cut}


# ---------------------------------------------------------------------------
# Region mechanics
# ---------------------------------------------------------------------------

def test_duplicate_region_names_uniquify():
    ldg = Ledger("t")

    @region("dot", ledger=ldg)
    def dot_a(x):
        return x.sum()

    @region("dot", ledger=ldg)
    def dot_b(x):
        return x.sum()

    assert dot_a.name == "dot" and dot_b.name == "dot#2"
    dot_a(jnp.ones(4))
    dot_b(jnp.ones(4))
    assert ldg.regions["dot"].calls == 1
    assert ldg.regions["dot#2"].calls == 1


def test_same_named_regions_from_different_ledgers_dont_merge():
    """An executor recording regions registered in OTHER ledgers must keep
    one row per region object, not merge by bare name."""
    @region("dot", ledger=Ledger("a"))
    def dot_a(x):
        return x.sum()

    @region("dot", ledger=Ledger("b"))
    def dot_b(x):
        return (x * x).sum()

    ex = Executor(UnifiedPolicy(), Ledger("shared"))
    ex.run(dot_a, jnp.ones(8))
    ex.run(dot_b, jnp.ones(8))
    ex.run(dot_a, jnp.ones(8))
    rows = {n: r.calls for n, r in ex.ledger.regions.items()}
    assert rows == {"dot": 2, "dot#2": 1}


def test_regions_are_hashable():
    @region("h", ledger=Ledger("t"))
    def h(x):
        return x

    assert h in {h}                     # usable as set/dict key
    assert len({h, h}) == 1


def test_region_dunder_name_is_identifier():
    @region("grad(p)", ledger=Ledger("t"))
    def grad_p(p):
        return p

    assert grad_p.__name__.isidentifier()
    assert grad_p.name == "grad(p)"


def test_default_size_uses_max_leaf():
    """A small scalar first arg must not mask the field size."""
    n = default_size((jnp.float32(0.5), jnp.ones(50000)), {})
    assert n == 50000
    assert default_size((), {}) == 0


def test_placement_hints_applied():
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    ldg = Ledger("t")

    @region("hinted", ledger=ldg, placement={0: host}, result_space=host)
    def hinted(x):
        return x + 1.0

    ex = Executor(UnifiedPolicy(), ldg)
    out = ex.run(hinted, jnp.ones(8192))
    assert space_of(out) == host.kind


def test_placement_hint_by_name_applies_to_positional_arg():
    host = preferred_host_space()
    if host is None:
        pytest.skip("no host memory space on this platform")
    @region("named-hint", ledger=Ledger("t"), placement={"x": host})
    def f(x):
        return x + 1.0

    ex = Executor(UnifiedPolicy(), Ledger("t"))
    # drive place_args directly: positional call must still hit the hint
    args, kwargs = ex.policy.placer.place_args(f, (jnp.ones(8192),), {})
    assert space_of(args[0]) == host.kind


def test_legacy_closure_adapts_to_region():
    calls = []

    def f(x):
        calls.append(1)
        return x * 2

    legacy = jax.jit(f)
    runner = lambda x: f(x)
    runner.jitted = legacy
    runner.offloaded = True
    runner.region_name = "legacy"
    r = as_region(runner)
    assert isinstance(r, Region)
    assert r.name == "legacy" and r.offloaded
    out = Executor(UnifiedPolicy()).run(runner, jnp.ones(4))
    np.testing.assert_allclose(np.asarray(out), 2.0)


# ---------------------------------------------------------------------------
# retired shims: the regions API is the only offload path
# ---------------------------------------------------------------------------

def test_no_internal_imports_of_retired_shims():
    """core/dispatch and core/executors are deprecation-alias stubs for
    external callers only; nothing in-repo may reference them (the same
    gate CI runs)."""
    import importlib.util
    import pathlib
    tool = pathlib.Path(__file__).resolve().parent.parent / "tools" / \
        "check_retired_imports.py"
    spec = importlib.util.spec_from_file_location("check_retired_imports",
                                                  tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check() == 0


def test_retired_shims_not_exported_from_core():
    import repro.core as core
    for retired in ("TargetDispatch", "DispatchStats", "offload",
                    "UnifiedExecutor", "DiscreteExecutor", "HostExecutor",
                    "make_executor", "BaseExecutor"):
        assert not hasattr(core, retired), \
            f"repro.core still exports retired shim {retired}"


def test_size_fn_override_respected():
    """Post-construction size_fn overrides must keep steering routing (the
    pre-regions dispatcher read size_fn on every call)."""
    ldg = Ledger("t")

    @region("f", ledger=ldg)
    def f(x):
        return x + 1

    ex = Executor(AdaptivePolicy(cutoff=100), ldg)
    f.size_fn = lambda args, kwargs: 0       # route everything to host
    ex.run(f, jnp.ones(1000))
    r = ldg.regions["f"]
    assert r.host_calls == 1 and r.device_calls == 0


def test_adaptive_executor_shares_ledger_with_staging_metrics():
    ldg = Ledger("shared")

    @region("f", ledger=ldg)
    def f(x):
        return x + 1

    ex = Executor(AdaptivePolicy(cutoff=100), ldg)
    ex.run(f, jnp.ones(10))
    ex.run(f, jnp.ones(1000))
    rep = ldg.coverage_report()
    assert rep["host_calls"] == 1 and rep["device_calls"] == 1
    assert "staging_fraction" in rep      # same report as staging metrics


def test_host_routed_region_returns_on_cpu_device():
    """A region routed to ``host`` moves its operands to the CPU device and
    returns there; entering ``jax.default_device`` alone would leave
    operands committed elsewhere (an accelerator, another memory space)
    where they are, and the call with them."""
    from repro.core.regions import host_device
    led = Ledger("host_route")

    @region("host_route", ledger=led)
    def twice(x):
        return 2.0 * x

    x = jax.device_put(jnp.arange(8.0), jax.sharding.SingleDeviceSharding(
        jax.devices()[0], memory_kind="pinned_host"))
    y = Executor(HostPolicy(), led).run(twice, x)
    assert y.devices() == {host_device()}
    assert space_of(y) == host_device().default_memory().kind
    np.testing.assert_array_equal(np.asarray(y), 2.0 * np.arange(8.0))
