"""Krylov solvers: PBiCGStab (paper listing 5) and PCG.

Two execution styles, same math:

* ``pbicgstab_regions`` — faithful to the paper's porting model: every
  region (Amul, preconditioner, each field macro, each reduction) is a
  separate offloaded region dispatched through an executor. On the
  ``discrete`` executor each region pays staging — the page-migration storm
  of Fig 6; on ``unified`` the alternation is free — the APU claim.
* ``pbicgstab_fused`` — the beyond-paper path: the whole solve is one jitted
  ``lax.while_loop`` (no host round-trips at all). This is what a TPU-native
  production deployment would run, and the delta vs. the region path is
  reported in the benchmarks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.cfd.dia import (DiaMatrix, STENCIL_OFFSETS, amul_pallas,
                           amul_ref, compose_offsets)
from repro.cfd.fields import fused_axpy_pallas, fused_axpbypz_pallas
from repro.cfd.precond import (RBDilu, jacobi_apply, rb_dilu_apply,
                               rb_dilu_factor, rb_dilu_pallas)
from repro.core.ledger import Ledger
from repro.core.regions import region

SMALL = 1e-20


@dataclasses.dataclass
class SolveResult:
    x: jax.Array
    iters: int
    initial_residual: float
    final_residual: float
    converged: bool


# ---------------------------------------------------------------------------
# Region-granular PBiCGStab (paper-faithful execution)
# ---------------------------------------------------------------------------

def make_solver_regions(ledger: Optional[Ledger] = None):
    # fresh Ledger when none given — repeated factory calls must not grow
    # the process-global ledger with uniquified duplicate rows
    kw = dict(ledger=ledger or Ledger("solver_regions"))

    # stencil declarations feed sharded replay (repro.core.shard_program):
    # halo width along the decomposed grid axis is inferred from the DIA
    # offsets; halo_args names the operands whose neighbors are read
    # pallas variants reuse the canonical lazy wrappers from dia / precond
    # / fields — one definition per kernel composition, many registrations
    @region("Amul", stencil=STENCIL_OFFSETS, halo_args=("x",), **kw)
    def amul_r(diag, off, x):
        return amul_ref(DiaMatrix(diag, off), x)

    amul_r.variant("pallas", amul_pallas)

    # the two half-sweeps chain (black reads updated red reads r): reach 2
    @region("precondition(DILU)",
            stencil=compose_offsets(STENCIL_OFFSETS, STENCIL_OFFSETS),
            halo_args=("r",), **kw)
    def precond_r(rdiag, red, off, r):
        return rb_dilu_apply(RBDilu(rdiag, red), DiaMatrix(rdiag * 0, off), r)

    precond_r.variant("pallas", rb_dilu_pallas)

    @region("sA=rA-alpha*AyA", **kw)
    def saxpy_r(a, x, y):
        return y - a * x

    @saxpy_r.variant("pallas")
    def _saxpy_k(a, x, y):
        # y - a*x is fused_axpy with the scale negated (exact)
        return fused_axpy_pallas(-a, x, y)

    @region("x+=a*yA+w*zA", **kw)
    def update_x_r(x, a, yA, w, zA):
        return x + a * yA + w * zA

    @update_x_r.variant("pallas")
    def _update_x_k(x, a, yA, w, zA):
        return fused_axpbypz_pallas(a, yA, w, zA, x)

    @region("p=r+beta*(p-w*v)", **kw)
    def update_p_r(r, beta, p, w, v):
        return r + beta * (p - w * v)

    @region("dot", **kw)
    def dot_r(x, y):
        return jnp.sum(x.astype(jnp.float64) * y.astype(jnp.float64))

    @region("sumMag", **kw)
    def summag_r(x):
        return jnp.sum(jnp.abs(x.astype(jnp.float64)))

    class R:
        amul, precond = amul_r, precond_r
        saxpy, update_x, update_p = saxpy_r, update_x_r, update_p_r
        dot, summag = dot_r, summag_r

    return R


def pbicgstab_regions(executor, regions, A: DiaMatrix, b, x0, P: RBDilu,
                      tol: float = 1e-6, rel_tol: float = 0.0,
                      max_iter: int = 500) -> SolveResult:
    """OpenFOAM PBiCGStab, one executor.run per offloaded region."""
    run = executor.run
    x = x0
    # r = b - 1.0*Ax through the saxpy region (identical math) so the whole
    # residual dataflow is region-visible — program capture
    # (repro.core.program) records real dependencies instead of freezing a
    # host-computed array as a constant
    r = run(regions.saxpy, 1.0, run(regions.amul, A.diag, A.off, x), b)
    rA0 = r
    norm = float(run(regions.summag, b)) + SMALL
    res0 = float(run(regions.summag, r)) / norm
    res = res0
    rho_old = alpha = omega = 1.0
    # zeros in device memory whatever space b was staged into
    p = jnp.zeros(b.shape, b.dtype)
    v = jnp.zeros(b.shape, b.dtype)
    it = 0
    while res > tol and (rel_tol <= 0 or res / max(res0, SMALL) > rel_tol) \
            and it < max_iter:
        rho = float(run(regions.dot, rA0, r))
        if abs(rho) < SMALL:
            break
        beta = (rho / rho_old) * (alpha / max(omega, SMALL))
        p = run(regions.update_p, r, beta, p, omega, v)
        yA = run(regions.precond, P.rdiag, P.red, A.off, p)
        v = run(regions.amul, A.diag, A.off, yA)
        denom = float(run(regions.dot, rA0, v))
        alpha = rho / (denom if abs(denom) > SMALL else SMALL)
        s = run(regions.saxpy, alpha, v, r)
        zA = run(regions.precond, P.rdiag, P.red, A.off, s)
        t = run(regions.amul, A.diag, A.off, zA)
        tt = float(run(regions.dot, t, t))
        ts = float(run(regions.dot, t, s))
        omega = ts / (tt if abs(tt) > SMALL else SMALL)
        x = run(regions.update_x, x, alpha, yA, omega, zA)
        r = run(regions.saxpy, omega, t, s)
        rho_old = rho
        res = float(run(regions.summag, r)) / norm
        it += 1
    return SolveResult(x, it, res0, res, res <= tol)


# ---------------------------------------------------------------------------
# Fused PBiCGStab (single jitted while_loop)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iter", "use_dilu"))
def pbicgstab_fused(A: DiaMatrix, b, x0, rdiag, red, tol: float = 1e-6,
                    max_iter: int = 500, use_dilu: bool = True):
    P = RBDilu(rdiag, red)

    def precond(r):
        return rb_dilu_apply(P, A, r) if use_dilu else jacobi_apply(A, r)

    def dot(a_, b_):
        return jnp.sum(a_.astype(jnp.float64) * b_.astype(jnp.float64))

    norm = jnp.sum(jnp.abs(b.astype(jnp.float64))) + SMALL
    r0 = b - amul_ref(A, x0)

    def res_of(r):
        return jnp.sum(jnp.abs(r.astype(jnp.float64))) / norm

    state = dict(x=x0, r=r0, rA0=r0, p=jnp.zeros_like(b), v=jnp.zeros_like(b),
                 rho=jnp.float64(1.0), alpha=jnp.float64(1.0),
                 omega=jnp.float64(1.0), it=jnp.int32(0), res=res_of(r0))

    def cond(st):
        return (st["res"] > tol) & (st["it"] < max_iter)

    def body(st):
        rho = dot(st["rA0"], st["r"])
        beta = (rho / jnp.where(jnp.abs(st["rho"]) < SMALL, SMALL, st["rho"])) \
            * (st["alpha"] / jnp.where(jnp.abs(st["omega"]) < SMALL, SMALL,
                                       st["omega"]))
        p = st["r"] + jnp.float32(beta) * (st["p"] - jnp.float32(st["omega"]) * st["v"])
        yA = precond(p)
        v = amul_ref(A, yA)
        denom = dot(st["rA0"], v)
        alpha = rho / jnp.where(jnp.abs(denom) < SMALL, SMALL, denom)
        s = st["r"] - jnp.float32(alpha) * v
        zA = precond(s)
        t = amul_ref(A, zA)
        tt = dot(t, t)
        omega = dot(t, s) / jnp.where(tt < SMALL, SMALL, tt)
        x = st["x"] + jnp.float32(alpha) * yA + jnp.float32(omega) * zA
        r = s - jnp.float32(omega) * t
        return dict(x=x, r=r, rA0=st["rA0"], p=p, v=v, rho=rho, alpha=alpha,
                    omega=omega, it=st["it"] + 1, res=res_of(r))

    out = jax.lax.while_loop(cond, body, state)
    return out["x"], out["it"], res_of(r0), out["res"]


def solve(A: DiaMatrix, b, x0, red, tol=1e-6, max_iter=500, use_dilu=True):
    """Convenience wrapper: factor + fused solve."""
    P = rb_dilu_factor(A, red)
    x, it, r0, res = pbicgstab_fused(A, b, x0, P.rdiag, P.red, tol=tol,
                                     max_iter=max_iter, use_dilu=use_dilu)
    return SolveResult(x, int(it), float(r0), float(res), float(res) <= tol)
