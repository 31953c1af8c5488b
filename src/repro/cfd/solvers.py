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
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.cfd.dia import (DiaMatrix, STENCIL_OFFSETS, amul_pallas,
                           amul_ref, compose_offsets)
from repro.cfd.fields import fused_axpy_pallas, fused_axpbypz_pallas
from repro.cfd.precond import (RBDilu, jacobi_apply, rb_dilu_apply,
                               rb_dilu_factor, rb_dilu_pallas)
from repro.core.ledger import SOLVE, Ledger, readback, span
from repro.core.regions import region

SMALL = 1e-20


@dataclasses.dataclass
class SolveResult:
    """``iters`` and the residuals are host numbers where a tolerance
    controlled the loop, and 0-d device arrays where it did not: no field
    is read back inside the solve."""
    x: jax.Array
    iters: Any
    initial_residual: Any
    final_residual: Any
    tol: float

    @property
    def converged(self):
        return self.final_residual <= self.tol


# ---------------------------------------------------------------------------
# Region-granular PBiCGStab (paper-faithful execution)
# ---------------------------------------------------------------------------

def _ratio(num, den):
    """alpha = rho / denom and omega = ts / tt: a denominator with
    ``|den| <= SMALL`` reads as SMALL."""
    return num / jnp.where(jnp.abs(den) > SMALL, den, SMALL)


def _factor(rho, num, den):
    """``_ratio`` as an update's scale, 0 once ``|rho| < SMALL``: an
    iteration whose rho broke down leaves x and r as they were."""
    return jnp.where(jnp.abs(rho) < SMALL, 0, _ratio(num, den))


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

    # the Krylov scalars arrive as 0-d arrays; each region works out the
    # alpha, beta or omega it uses from the scalars they come from
    @region("sA=rA-alpha*AyA", **kw)
    def saxpy_r(rho, num, den, x, y):
        return y - _factor(rho, num, den) * x

    @saxpy_r.variant("pallas")
    def _saxpy_k(rho, num, den, x, y):
        # y - a*x is fused_axpy with the scale negated (exact)
        return fused_axpy_pallas(-_factor(rho, num, den), x, y)

    @region("x+=a*yA+w*zA", **kw)
    def update_x_r(x, rho, denom, yA, ts, tt, zA):
        return x + _factor(rho, rho, denom) * yA + _factor(rho, ts, tt) * zA

    @update_x_r.variant("pallas")
    def _update_x_k(x, rho, denom, yA, ts, tt, zA):
        return fused_axpbypz_pallas(_factor(rho, rho, denom), yA,
                                    _factor(rho, ts, tt), zA, x)

    @region("p=r+beta*(p-w*v)", **kw)
    def update_p_r(r, rho, rho_old, denom, ts, tt, p, v):
        # alpha and omega of the last iteration, from its rho, denom, ts, tt
        omega = _ratio(ts, tt)
        beta = (rho / rho_old) * (_ratio(rho_old, denom)
                                  / jnp.maximum(omega, SMALL))
        return jnp.where(jnp.abs(rho) < SMALL, p, r + beta * (p - omega * v))

    @region("dot", **kw)
    def dot_r(x, y):
        return jnp.sum(x.astype(jnp.float64) * y.astype(jnp.float64))

    @region("sumMag", **kw)
    def summag_r(x):
        return jnp.sum(jnp.abs(x.astype(jnp.float64)))

    @region("res=sumMag(r)/norm", **kw)
    def residual_r(r, norm, rho, it):
        """The normalised residual; the iteration count, advanced unless
        rho broke down; and what a tolerance-controlled loop reads: the
        residual, or -1 once rho broke down."""
        live = ~(jnp.abs(rho) < SMALL)      # the freeze's test, NaN live
        res = jnp.sum(jnp.abs(r.astype(jnp.float64))) / (norm + SMALL)
        return res, it + live, jnp.where(live, res, -1)

    class R:
        amul, precond = amul_r, precond_r
        saxpy, update_x, update_p = saxpy_r, update_x_r, update_p_r
        dot, summag, residual = dot_r, summag_r, residual_r

    return R


def pbicgstab_regions(executor, regions, A: DiaMatrix, b, x0, P: RBDilu,
                      tol: float = 1e-6, rel_tol: float = 0.0,
                      max_iter: int = 500) -> SolveResult:
    """OpenFOAM PBiCGStab, one executor.run per offloaded region.

    The Krylov scalars stay 0-d device arrays between the regions. An
    iteration whose rho broke down (``|rho| < SMALL``) leaves x, r and p
    as they were, so the next sees the same rho and changes nothing
    either. The host reads only what controls the loop, each read through
    ``readback`` (counted on the executor's ledger where it has one): with
    ``tol <= 0`` and ``rel_tol <= 0`` nothing, and the solve runs
    ``max_iter`` iterations; else the entry residual, then one value an
    iteration that carries the residual and the breakdown."""
    with span(SOLVE):
        run = executor.run
        ledger = getattr(executor, "ledger", None)
        controlled = tol > 0 or rel_tol > 0
        # in the dtype the dot region returns; region-made from here on
        one = jnp.ones((), jax.dtypes.canonicalize_dtype(jnp.float64))
        it = jnp.zeros((), jnp.int32)
        x = x0
        # r = b - 1.0*Ax through the saxpy region (identical math) so the
        # whole residual dataflow is region-visible — program capture
        # (repro.core.program) records real dependencies instead of
        # freezing a host-computed array as a constant
        r = run(regions.saxpy, one, one, one,
                run(regions.amul, A.diag, A.off, x), b)
        rA0 = r
        norm = run(regions.summag, b)
        res0, _, _ = run(regions.residual, r, norm, one, it)
        res = res0
        if controlled:
            res0 = res = readback(res0, ledger)
        # the last iteration's rho, denom, ts and tt give beta its alpha
        # and omega: 1.0 each before the first
        rho_old = denom = ts = tt = one
        # zeros in device memory whatever space b was staged into
        p = jnp.zeros(b.shape, b.dtype)
        v = jnp.zeros(b.shape, b.dtype)
        n = 0
        while n < max_iter and (not controlled or (res > tol and (
                rel_tol <= 0 or res / max(res0, SMALL) > rel_tol))):
            rho = run(regions.dot, rA0, r)
            p = run(regions.update_p, r, rho, rho_old, denom, ts, tt, p, v)
            yA = run(regions.precond, P.rdiag, P.red, A.off, p)
            v = run(regions.amul, A.diag, A.off, yA)
            denom = run(regions.dot, rA0, v)
            s = run(regions.saxpy, rho, rho, denom, v, r)
            zA = run(regions.precond, P.rdiag, P.red, A.off, s)
            t = run(regions.amul, A.diag, A.off, zA)
            tt = run(regions.dot, t, t)
            ts = run(regions.dot, t, s)
            x = run(regions.update_x, x, rho, denom, yA, ts, tt, zA)
            r = run(regions.saxpy, rho, ts, tt, t, s)
            rho_old = rho
            res_d, it, signal = run(regions.residual, r, norm, rho, it)
            if controlled:
                value = readback(signal, ledger)
                if value < 0:
                    break           # rho broke down: nothing changed
                res = value
            else:
                res = res_d
            n += 1
        if controlled:
            it = n
        return SolveResult(x, it, res0, res, tol)


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
    return SolveResult(x, int(it), float(r0), float(res), tol)
