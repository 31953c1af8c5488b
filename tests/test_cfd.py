"""CFD validation: operators vs dense algebra, two-color DILU vs sequential
DILU (iteration parity), SIMPLE convergence, executor equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cfd import fvm
from repro.cfd.dia import DiaMatrix, amul_ref, to_dense
from repro.cfd.grid import Grid
from repro.cfd.precond import (dilu_seq_ref, jacobi_apply, rb_dilu_apply,
                               rb_dilu_factor)
from repro.cfd.simple import SimpleConfig, SimpleFoam, init_state
from repro.cfd.solvers import (make_solver_regions, pbicgstab_fused,
                               pbicgstab_regions, solve)
from repro.core.ledger import Ledger
from repro.core.regions import (DiscretePolicy, Executor, HostPolicy,
                                UnifiedPolicy)


def test_amul_matches_dense(rng):
    g = Grid((4, 3, 5))
    A, _ = fvm.laplacian(g, 2.0)
    x = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    y = amul_ref(A, x)
    yd = (to_dense(A) @ np.asarray(x, np.float64).ravel()).reshape(g.shape)
    np.testing.assert_allclose(np.asarray(y), yd, rtol=1e-4, atol=1e-4)


def test_laplacian_spd(rng):
    g = Grid((4, 4, 4))
    A, _ = fvm.laplacian(g, 1.0)
    M = to_dense(A)
    np.testing.assert_allclose(M, M.T, atol=1e-12)   # symmetric
    w = np.linalg.eigvalsh(M)
    assert w.min() > 0                                # positive definite


def test_transpose_matches_dense(rng):
    g = Grid((3, 4, 2))
    phi = jnp.asarray(rng.randn(6, *g.shape).astype(np.float32))
    A = fvm.div_upwind(g, phi)     # non-symmetric
    At = A.transpose()
    np.testing.assert_allclose(to_dense(At), to_dense(A).T, atol=1e-5)


def test_rb_dilu_iteration_parity(rng):
    """Two-color DILU must precondition comparably to sequential DILU:
    same solve within +-50% iterations, and much better than none."""
    g = Grid((8, 8, 8))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    red, _ = g.red_black_masks()
    r_dilu = solve(A, b, jnp.zeros_like(b), red, tol=1e-6, max_iter=300)
    r_jac = solve(A, b, jnp.zeros_like(b), red, tol=1e-6, max_iter=300,
                  use_dilu=False)
    assert r_dilu.converged
    assert r_dilu.iters <= r_jac.iters            # DILU no worse than Jacobi
    assert r_dilu.iters <= 0.8 * r_jac.iters + 2  # and materially better


def test_rb_dilu_is_exact_inverse_of_its_M(rng):
    """M^-1 applied via sweeps must invert M = (L+D*)D*^-1(D*+U) exactly."""
    g = Grid((4, 4, 2))
    A, _ = fvm.laplacian(g, 1.0)
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    r = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    w = rb_dilu_apply(P, A, r)
    # rebuild M densely in the SAME (natural) index space
    N = g.n
    M = to_dense(A).copy()
    redv = np.asarray(red).ravel()
    dstar = np.where(redv, np.asarray(A.diag).ravel(),
                     1.0 / np.asarray(P.rdiag).ravel())
    Lm = np.zeros((N, N)); Um = np.zeros((N, N))
    for i in range(N):
        for j in range(N):
            if i == j or M[i, j] == 0:
                continue
            # ordering: red before black
            before = (redv[j] and not redv[i])
            if before:
                Lm[i, j] = M[i, j]
            elif redv[i] and not redv[j]:
                Um[i, j] = M[i, j]
    Mfull = (Lm + np.diag(dstar)) @ np.diag(1.0 / dstar) @ (np.diag(dstar) + Um)
    w2 = np.linalg.solve(Mfull, np.asarray(r, np.float64).ravel())
    np.testing.assert_allclose(np.asarray(w).ravel(), w2, rtol=2e-3, atol=2e-4)


def test_pbicgstab_regions_matches_fused(rng):
    g = Grid((8, 8, 8))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    ldg = Ledger("t")
    regions = make_solver_regions(ldg)
    ex = Executor(UnifiedPolicy(), ldg)
    r1 = pbicgstab_regions(ex, regions, A, b, jnp.zeros_like(b), P, tol=1e-6)
    r2 = solve(A, b, jnp.zeros_like(b), red, tol=1e-6)
    assert r1.converged and r2.converged
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x),
                               rtol=5e-3, atol=5e-4)


def laplacian_8(rng):
    g = Grid((8, 8, 8))
    A, _ = fvm.laplacian(g, 1.0)
    red, _ = g.red_black_masks()
    b = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    return A, b, rb_dilu_factor(A, red)


@pytest.mark.parametrize("case", ["zero", "consistent"])
def test_pbicgstab_regions_freezes_on_breakdown(rng, case):
    """rho is 0 in the first iteration (r = b - A x0 = 0): no tolerance
    stops the loop, yet every iteration leaves x as it was and counts
    nothing."""
    A, _, P = laplacian_8(rng)
    ex = Executor(UnifiedPolicy(), Ledger("t"))
    regions = make_solver_regions(ex.ledger)
    if case == "zero":
        x0 = jnp.zeros(A.diag.shape, jnp.float32)
        b = jnp.zeros_like(x0)
    else:
        x0 = jnp.asarray(rng.randn(*A.diag.shape).astype(np.float32))
        b = ex.run(regions.amul, A.diag, A.off, x0)   # the same executable
    r = pbicgstab_regions(ex, regions, A, b, x0, P, tol=0.0, max_iter=3)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(x0))
    assert int(r.iters) == 0
    assert float(r.final_residual) == float(r.initial_residual) == 0.0
    assert ex.ledger.readbacks == 0


class _RhoBreaksDown:
    """Forwards to an Executor, but every ``dot`` that makes rho from
    iteration ``at`` (1-based) on returns 0."""

    def __init__(self, ex, regions, at):
        self.ex, self.ledger, self.dot = ex, ex.ledger, regions.dot
        self.dots, self.at = 0, at

    def run(self, region, *args):
        out = self.ex.run(region, *args)
        if region is self.dot:
            self.dots += 1
            if self.dots > 4 * (self.at - 1) and self.dots % 4 == 1:
                return jnp.zeros_like(out)
        return out


@pytest.mark.parametrize("tol", [0.0, 1e-12])
def test_pbicgstab_regions_breakdown_mid_solve(rng, tol):
    """rho breaks down in the second iteration: the solve ends with the
    iterate, residual and count of the first. Under a tolerance the host
    stops after that iteration; without one the device freezes every
    iteration left."""
    A, b, P = laplacian_8(rng)
    ex = Executor(UnifiedPolicy(), Ledger("t"))
    regions = make_solver_regions(ex.ledger)
    x0 = jnp.zeros_like(b)
    one = pbicgstab_regions(ex, regions, A, b, x0, P, tol=tol, max_iter=1)
    broken = _RhoBreaksDown(Executor(UnifiedPolicy(), Ledger("b")),
                            regions, at=2)
    r = pbicgstab_regions(broken, regions, A, b, x0, P, tol=tol,
                          max_iter=5)
    assert int(r.iters) == 1
    assert float(r.final_residual) == float(one.final_residual)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(one.x))
    if tol > 0:       # entry, then two iterations
        assert broken.dots == 8 and broken.ledger.readbacks == 1 + 2
    else:
        assert broken.dots == 4 * 5 and broken.ledger.readbacks == 0


def test_pbicgstab_regions_stops_where_fused_does(rng):
    A, b, P = laplacian_8(rng)
    ex = Executor(UnifiedPolicy(), Ledger("t"))
    regions = make_solver_regions(ex.ledger)
    x0 = jnp.zeros_like(b)
    r1 = pbicgstab_regions(ex, regions, A, b, x0, P, tol=1e-6)
    x2, it2, _, res2 = pbicgstab_fused(A, b, x0, P.rdiag, P.red, tol=1e-6)
    assert r1.iters == int(it2) > 1
    assert r1.converged and float(res2) <= 1e-6
    assert ex.ledger.readbacks == 1 + r1.iters
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(x2),
                               rtol=1e-5, atol=1e-6)


def test_executors_same_result(rng):
    """unified / discrete / host must be numerically identical paths."""
    g = Grid((6, 6, 6))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    outs = []
    for make in (UnifiedPolicy, DiscretePolicy, HostPolicy):
        ldg = Ledger("t")
        regions = make_solver_regions(ldg)
        r = pbicgstab_regions(Executor(make(), ldg), regions, A, b,
                              jnp.zeros_like(b), P, tol=1e-6)
        outs.append(np.asarray(r.x))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-5, atol=1e-6)


def test_discrete_executor_pays_staging(rng):
    g = Grid((12, 12, 12))
    A, _ = fvm.laplacian(g, 1.0)
    b = jnp.asarray(rng.rand(*g.shape).astype(np.float32))
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    ldg = Ledger("t")
    regions = make_solver_regions(ldg)
    ex = Executor(DiscretePolicy(), ldg)
    r = pbicgstab_regions(ex, regions, A, b, jnp.zeros_like(b), P, tol=1e-6)
    rep = ex.report()
    assert rep["staging_fraction"] > 0.05
    assert rep["staging_s"] > 0
    # uniform return contract: staged results are host-space jax Arrays,
    # not numpy (the old DiscreteExecutor changed types per mode)
    assert isinstance(r.x, jax.Array)


def test_simple_foam_converges():
    from repro.cfd import fvc
    cfg = SimpleConfig(grid=Grid((10, 10, 10)), nu=0.1, inner_max=40)
    app = SimpleFoam(cfg)
    st = init_state(cfg)

    def div_inf(s):
        return float(jnp.abs(fvc.div_flux(
            cfg.grid, fvm.face_fluxes(cfg.grid, s.u, s.v, s.w))).max())

    st, _, _ = app.run_steps(st, 3)
    d1 = div_inf(st)
    st, _, _ = app.run_steps(st, 7)
    d2 = div_inf(st)
    assert np.isfinite(np.asarray(st.u)).all()
    assert np.isfinite(np.asarray(st.p)).all()
    # velocities bounded by the lid scale (stability) and flow develops
    assert float(jnp.abs(st.u).max()) < 2.0 * cfg.lid_velocity
    assert float(jnp.abs(st.u).max()) > 0.05
    # SIMPLE drives the field toward divergence-free
    assert d2 < d1
