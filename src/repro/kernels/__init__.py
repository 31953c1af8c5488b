"""Custom-kernel packages and their implementation-variant tables.

Each subpackage ``<name>/`` is one compute hot-spot with three files:

* ``kernel.py`` — the Pallas implementation (compiled by Mosaic when
  lowered for a TPU, interpreted on any other platform: ``_call.py``),
* ``ref.py``    — the pure-jnp oracle: the **ref** variant and the
  semantics anchor every other variant is tested against,
* ``ops.py``    — jitted public wrappers (used by the kernel's own tests).

The packages do NOT wire themselves into application code.  Application
regions declare them as named variants (``@some_region.variant("pallas")``,
``repro.core.regions``) and the executing policy's Selector axis picks one
per call — OpenMP 5.2's ``declare variant`` dispatch (docs/VARIANTS.md).
The live registrations are in ``repro.cfd.dia`` / ``precond`` / ``fields``
/ ``solvers`` and ``repro.models.rwkv6``.

Contract: every op of every package MUST carry a ``ref`` entry in
:func:`variant_tables` (CI runs :func:`check_ref_variants`), so the
declare-variant fallback — and the parity tests in tests/test_variants.py
— always have a base function to land on.
"""
from __future__ import annotations

from typing import Callable, Dict

#: the variant every kernel package must provide (the fallback target)
REQUIRED_VARIANT = "ref"

#: kernel subpackages participating in the variant contract
PACKAGES = ("stencil_spmv", "fused_field", "rwkv6_scan")


def variant_tables() -> Dict[str, Dict[str, Dict[str, Callable]]]:
    """``{package: {op: {variant: callable}}}`` for every kernel package.

    Imported lazily so merely importing ``repro.kernels`` never pulls the
    Pallas toolchain; callables are the *unjitted* implementations, ready
    for ``Region.variant`` registration or direct jitting."""
    from repro.kernels.fused_field import kernel as ffk, ref as ffr
    from repro.kernels.rwkv6_scan import kernel as rwk, ref as rwr
    from repro.kernels.stencil_spmv import kernel as ssk, ref as ssr

    return {
        "stencil_spmv": {
            "amul": {"ref": ssr.stencil_spmv, "pallas": ssk.stencil_spmv},
            "rb_dilu": {"ref": ssr.rb_dilu, "pallas": ssk.rb_dilu},
        },
        "fused_field": {
            "axpy": {"ref": ffr.fused_axpy, "pallas": ffk.fused_axpy},
            "xpay": {"ref": ffr.fused_xpay, "pallas": ffk.fused_xpay},
            "mul": {"ref": ffr.fused_mul, "pallas": ffk.fused_mul},
            "axpbypz": {"ref": ffr.fused_axpbypz,
                        "pallas": ffk.fused_axpbypz},
        },
        "rwkv6_scan": {
            "scan": {"ref": rwr.rwkv6_scan, "pallas": rwk.rwkv6_scan},
        },
    }


def _live_kernel_regions():
    """The Region objects that actually register kernel variants — the
    registrations the declare-variant fallback depends on at runtime."""
    from repro.cfd.dia import AMUL
    from repro.cfd.fields import make_field_ops
    from repro.cfd.precond import RB_DILU
    from repro.cfd.solvers import make_solver_regions
    from repro.models.rwkv6 import RWKV6_SCAN
    ops = make_field_ops()
    solver = make_solver_regions()
    return [AMUL, RB_DILU, RWKV6_SCAN,
            solver.amul, solver.precond, solver.saxpy, solver.update_x,
            ops.axpy, ops.xpay, ops.axpbypz, ops.fmul]


def check_ref_variants() -> Dict[str, int]:
    """Fail (SystemExit) unless every op of every kernel package ships a
    ``ref`` entry in :func:`variant_tables` AND every live kernel-backed
    Region registration carries both ``ref`` and a kernel variant; returns
    ``{package: op count}`` on success.  CI runs this as a dedicated job
    step.  Checking the real Region objects (not just the table literal)
    is what catches a package wired into application regions without a
    base-function fallback."""
    tables = variant_tables()
    missing = [pkg for pkg in PACKAGES if pkg not in tables]
    missing += [f"{pkg}.{op}" for pkg, ops in tables.items()
                for op, table in ops.items()
                if REQUIRED_VARIANT not in table]
    for r in _live_kernel_regions():
        if REQUIRED_VARIANT not in r.variants:
            missing.append(f"region:{r.name}")
        if len(r.variants) < 2:        # kernel-backed: ref alone is a lie
            missing.append(f"region:{r.name} (no kernel variant)")
    if missing:
        raise SystemExit(
            f"kernel packages/regions without a {REQUIRED_VARIANT!r} "
            f"variant: {missing}")
    return {pkg: len(ops) for pkg, ops in tables.items()}
