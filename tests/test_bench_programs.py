"""``bench/programs.json`` names, per region, the XLA modules that run it;
the roofline metrics read a region's device time through it.  A renamed
variant function would change its module name and silently drop the
metric, so every variant of the regions it lists must compile to a module
it names."""
import json
import re
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.cfd import fvm
from repro.cfd.grid import Grid
from repro.cfd.precond import rb_dilu_factor
from repro.cfd.solvers import make_solver_regions
from repro.core.ledger import Ledger

PROGRAMS = Path(__file__).resolve().parent.parent / "bench" / "programs.json"


def module_name(region, impl, args) -> str:
    text = region.jitted_variant(impl, donate=False).lower(*args).as_text()
    return re.search(r"module @(\S+)", text).group(1)


@pytest.mark.parametrize("attr,name", [("amul", "Amul"),
                                       ("precond", "precondition(DILU)")])
def test_every_variant_is_listed_under_its_region(attr, name):
    g = Grid((8, 8, 8))
    A, _ = fvm.laplacian(g, 1.0)
    red, _ = g.red_black_masks()
    P = rb_dilu_factor(A, red)
    x = jnp.ones(g.shape, jnp.float32)
    args = {"amul": (A.diag, A.off, x),
            "precond": (P.rdiag, P.red, A.off, x)}[attr]
    region = getattr(make_solver_regions(Ledger("programs")), attr)
    assert region.name == name
    listed = json.loads(PROGRAMS.read_text())["regions"][name]
    assert set(region.variants) >= {"ref", "pallas"}
    for impl in region.variants:
        assert module_name(region, impl, args) in listed, impl
