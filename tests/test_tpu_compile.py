"""Compile the Pallas kernels of the main path for a described TPU v5e.

Nothing runs: the TPU compiler (installed with jax) compiles each kernel
for one chip of a ``v5e:2x2`` topology that is described, not attached,
and refuses what the chip would refuse (unaligned slices, loads from HBM
refs, ops without a Mosaic lowering). Each compiled program must carry
the kernel as a ``tpu_custom_call``, i.e. the Mosaic branch was taken and
not the interpreter.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_field import kernel as FF
from repro.kernels.stencil_spmv import kernel as SS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler: nothing to check here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [128, 100])
def test_stencil_spmv_compiles(n, one_chip, no_cache):
    g = (n, n, n)
    text = _compiled_text(SS.stencil_spmv, g, (6,) + g, g, sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [128, 100])
def test_rb_dilu_compiles(n, one_chip, no_cache):
    g = (n, n, n)
    text = _compiled_text(SS.rb_dilu, g, g, (6,) + g, g, sharding=one_chip)
    assert "tpu_custom_call" in text


G = (128, 128, 128)


@pytest.mark.parametrize("fn,shapes", [
    (lambda a, x, y: FF.fused_axpy(a[0], x, y), [(1,), G, G]),
    (lambda a, x, b, y, z: FF.fused_axpbypz(a[0], x, b[0], y, z),
     [(1,), G, (1,), G, G]),
], ids=["axpy", "axpbypz"])
def test_fused_field_compiles(fn, shapes, one_chip, no_cache):
    assert "tpu_custom_call" in _compiled_text(fn, *shapes, sharding=one_chip)

