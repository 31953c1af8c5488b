"""Paper-core unit + property tests: pool invariants (hypothesis), adaptive
routing, ledger coverage, memory placement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.ledger import Ledger, offload_region
from repro.core.pool import (HostStagingPool, POOL_MIN_ELEMS, _size_class)
from repro.core.regions import AdaptivePolicy, Executor, Region, region
from repro.core.umem import MemSpace, place, space_of, supported_spaces


class TestPoolProperties:
    @given(st.lists(st.tuples(st.integers(1, 200_000), st.booleans()),
                    min_size=1, max_size=40))
    @settings(max_examples=50, deadline=None)
    def test_acquire_release_invariants(self, ops_list):
        pool = HostStagingPool()
        held = []
        for n, do_release in ops_list:
            a = pool.acquire((n,), np.float32)
            assert a.shape == (n,) and a.dtype == np.float32
            held.append(a)
            if do_release and held:
                pool.release(held.pop())
        s = pool.stats
        # pooled buffers only above the paper's 5K threshold
        assert s.unpooled == sum(1 for n, _ in ops_list if n < POOL_MIN_ELEMS)
        assert s.hits + s.misses == sum(1 for n, _ in ops_list
                                        if n >= POOL_MIN_ELEMS)
        # a released class must be reusable: free bytes consistent
        assert pool.free_bytes >= 0

    @given(st.integers(1, 1 << 30))
    @settings(max_examples=200, deadline=None)
    def test_size_class_sane(self, n):
        c = _size_class(n)
        assert c >= max(n, 4096) and c < 2 * max(n, 4096)

    def test_reuse_is_real(self):
        pool = HostStagingPool()
        a = pool.acquire((8192,), np.float32)
        raw = a._pool_raw
        pool.release(a)
        b = pool.acquire((8192,), np.float32)
        assert b._pool_raw is raw            # same backing memory
        assert pool.stats.hit_rate == 0.5


class TestAdaptiveRouting:
    """The ``if(target: n > TARGET_CUT_OFF)`` clause on the regions API —
    the behaviors the retired TargetDispatch shim used to cover."""

    def test_cutoff_routes(self):
        ldg = Ledger("t")

        @region("inc", ledger=ldg)
        def inc(x):
            return x + 1

        ex = Executor(AdaptivePolicy(cutoff=100), ldg)
        ex.run(inc, jnp.ones(10))
        ex.run(inc, jnp.ones(1000))
        r = ldg.regions["inc"]
        assert r.host_calls == 1 and r.device_calls == 1
        assert 0 < r.offload_fraction < 1

    def test_results_identical_both_paths(self):
        ldg = Ledger("t")

        @region("sin2", ledger=ldg)
        def sin2(x):
            return jnp.sin(x) * 2

        ex = Executor(AdaptivePolicy(cutoff=50), ldg)
        np.testing.assert_allclose(
            np.asarray(ex.run(sin2, jnp.linspace(0, 1, 10))),
            np.sin(np.linspace(0, 1, 10)) * 2, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(ex.run(sin2, jnp.linspace(0, 1, 1000))),
            np.sin(np.linspace(0, 1, 1000)) * 2, rtol=1e-6)
        r = ldg.regions["sin2"]
        assert r.host_calls == 1 and r.device_calls == 1

    def test_decorator(self):
        @region("triple", ledger=Ledger("t"))
        def f(x):
            return x * 3

        assert isinstance(f, Region)
        out = Executor(AdaptivePolicy(cutoff=10), Ledger("t")).run(
            f, jnp.ones(5))
        np.testing.assert_allclose(np.asarray(out), 3.0)


class TestLedger:
    def test_coverage(self):
        ldg = Ledger("t")

        @offload_region("hot", ledger=ldg)
        def hot(x):
            return x * 2

        @offload_region("cold", offloaded=False, ledger=ldg)
        def cold(x):
            return x + 1

        hot(jnp.ones(100))
        cold(jnp.ones(100))
        rep = ldg.coverage_report()
        assert rep["regions"] == 2 and rep["offloaded_regions"] == 1
        assert 0 < rep["device_fraction"] < 1


class TestUmem:
    def test_placement(self):
        if "pinned_host" not in supported_spaces():
            pytest.skip("no host memory space")
        x = place(jnp.ones(100), MemSpace.HOST)
        assert space_of(x) == "pinned_host"
        y = place(x, MemSpace.DEVICE)
        assert space_of(y) == "device"
        np.testing.assert_array_equal(np.asarray(y), 1.0)


def test_compile_cache_dir_honours_env_else_fixed_path(monkeypatch,
                                                         tmp_path):
    """The entry points' cache helper leaves JAX's reading of
    JAX_COMPILATION_CACHE_DIR alone when it is set, and otherwise points
    the cache at the same ``<checkout>/.jax_cache`` on every call."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch import compilation as C
    saved = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert C.configure_compilation() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = C.configure_compilation(), C.configure_compilation()
        assert first == second == str(C.CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert (C.CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
        compilation_cache.reset_cache()
