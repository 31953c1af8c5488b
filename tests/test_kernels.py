"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (assignment
requirement for every Pallas kernel)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import fvm
from repro.cfd.grid import Grid
from repro.cfd.precond import rb_dilu_factor


class TestFusedField:
    @pytest.mark.parametrize("shape", [(33,), (128, 128), (17, 5, 9),
                                       (64 * 128 + 3,)])
    @pytest.mark.parametrize("dt", ["float32", "bfloat16"])
    def test_axpy_xpay_mul(self, shape, dt, rng):
        from repro.kernels.fused_field import ops as K, ref as R
        x = jnp.asarray(rng.rand(*shape), dt)
        y = jnp.asarray(rng.rand(*shape), dt)
        z = jnp.asarray(rng.rand(*shape), dt)
        tol = dict(rtol=2e-2 if dt == "bfloat16" else 1e-5, atol=1e-2 if dt == "bfloat16" else 1e-6)
        for kf, rf, args in [(K.fused_axpy, R.fused_axpy, (2.5, x, y)),
                             (K.fused_xpay, R.fused_xpay, (-1.25, x, y)),
                             (K.fused_mul, R.fused_mul, (x, y)),
                             (K.fused_axpbypz, R.fused_axpbypz,
                              (2.0, x, -0.5, y, z))]:
            np.testing.assert_allclose(np.asarray(kf(*args), np.float32),
                                       np.asarray(rf(*args), np.float32),
                                       **tol)


class TestStencilSpmv:
    @pytest.mark.parametrize("shape", [(8, 6, 10), (16, 16, 16), (5, 7, 3),
                                       (32, 16, 8), (3, 3, 3)])
    def test_amul_vs_ref(self, shape, rng):
        from repro.kernels.stencil_spmv import ops as K, ref as R
        g = Grid(shape)
        A, _ = fvm.laplacian(g, 1.0)
        x = jnp.asarray(rng.rand(*shape).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(K.stencil_spmv(A.diag, A.off, x)),
            np.asarray(R.stencil_spmv(A.diag, A.off, x)),
            rtol=3e-4, atol=1e-4)

    @pytest.mark.parametrize("shape", [(8, 6, 10), (16, 16, 16), (6, 4, 12)])
    def test_rb_dilu_vs_ref(self, shape, rng):
        from repro.kernels.stencil_spmv import ops as K, ref as R
        g = Grid(shape)
        A, _ = fvm.laplacian(g, 1.0)
        red, _ = g.red_black_masks()
        P = rb_dilu_factor(A, red)
        r = jnp.asarray(rng.rand(*shape).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(K.rb_dilu_apply(P.rdiag, red, A.off, r)),
            np.asarray(R.rb_dilu(P.rdiag, red, A.off, r)),
            rtol=3e-4, atol=1e-4)


class TestRwkv6Scan:
    @pytest.mark.parametrize("dims", [(2, 128, 2, 16, 32), (1, 64, 3, 8, 64),
                                      (2, 96, 1, 32, 16), (1, 32, 2, 8, 8)])
    def test_vs_sequential(self, dims, rng):
        from repro.kernels.rwkv6_scan import ops as K, ref as R
        B, T, H, hd, C = dims
        r, k, v = [jnp.asarray(rng.randn(B, T, H, hd).astype(np.float32)) * 0.5
                   for _ in range(3)]
        logw = -jnp.asarray(rng.rand(B, T, H, hd).astype(np.float32)) * 2 - 0.01
        u = jnp.asarray(rng.randn(H, hd).astype(np.float32)) * 0.3
        ko, ks = K.rwkv6_scan(r, k, v, logw, u, chunk=C)
        ro, rs = R.rwkv6_scan(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(ko), np.asarray(ro),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(ks), np.asarray(rs),
                                   rtol=2e-4, atol=2e-4)

    def test_chunked_jax_path_matches_too(self, rng):
        from repro.kernels.rwkv6_scan import ref as R
        B, T, H, hd = 2, 128, 2, 16
        r, k, v = [jnp.asarray(rng.randn(B, T, H, hd).astype(np.float32)) * 0.5
                   for _ in range(3)]
        logw = -jnp.asarray(rng.rand(B, T, H, hd).astype(np.float32)) - 0.01
        u = jnp.asarray(rng.randn(H, hd).astype(np.float32)) * 0.3
        co, cs = R.rwkv6_chunked(r, k, v, logw, u, chunk=32)
        ro, rs = R.rwkv6_scan(r, k, v, logw, u)
        np.testing.assert_allclose(np.asarray(co), np.asarray(ro),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("op", ["fused_axpy", "stencil_spmv"])
def test_interpret_choice_follows_lowering_platform(op):
    """One jitted kernel call: lowered for a TPU it is a Mosaic custom
    call, lowered for the CPU it runs in the Pallas interpreter. The choice
    is made per lowering, not at import or from the default backend."""
    import jax
    from repro.kernels.fused_field import kernel as FF
    from repro.kernels.stencil_spmv import kernel as SS
    x = jnp.ones((8, 8, 8), jnp.float32)
    fn, args = {"fused_axpy": (FF.fused_axpy, (2.0, x, x)),
                "stencil_spmv": (SS.stencil_spmv,
                                 (x, jnp.stack([x] * 6), x))}[op]
    traced = jax.jit(fn).trace(*args)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" in tpu
    assert "tpu_custom_call" not in cpu
