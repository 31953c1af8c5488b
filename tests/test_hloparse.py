"""HLO parser validation: trip-count-scaled flops must equal the unrolled
program's flops; collectives found and scaled."""
import subprocess
import sys

CODE = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hloparse

x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

def scanned(x, w):
    def body(c, _):
        return c @ w, None
    y, _ = jax.lax.scan(body, x, None, length=10)
    return y

def unrolled(x, w):
    for _ in range(10):
        x = x @ w
    return x

fs = hloparse.analyze(jax.jit(scanned).lower(x, x).compile().as_text()).flops
fu = hloparse.analyze(jax.jit(unrolled).lower(x, x).compile().as_text()).flops
assert abs(fs - fu) / fu < 0.01, (fs, fu)
assert abs(fu - 10 * 2 * 256**3) / (10 * 2 * 256**3) < 0.01

from repro.launch.mesh import make_smoke_mesh
mesh = make_smoke_mesh((8,), ("model",))
def sharded(x, w):
    def body(c, _):
        y = jax.lax.with_sharding_constraint(
            c @ w, NamedSharding(mesh, P(None, "model")))
        y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))
        return y, None
    y, _ = jax.lax.scan(body, x, None, length=5)
    return y
c = jax.jit(sharded, in_shardings=(NamedSharding(mesh, P()),
                                   NamedSharding(mesh, P(None, "model"))))
r = hloparse.analyze(c.lower(x, x).compile().as_text())
ag = r.collectives.get("all-gather", {})
assert ag.get("count") == 5.0, r.collectives       # scaled by trip count
assert abs(r.flops - 5 * 2 * 256**3 / 8) / (5 * 2 * 256**3 / 8) < 0.01
print("HLOPARSE_OK")
'''


def test_hloparse_subprocess():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=300)
    assert "HLOPARSE_OK" in r.stdout, r.stderr[-2000:]


def test_shape_bytes():
    from repro.launch.hloparse import shape_elems_bytes
    assert shape_elems_bytes("f32[128,4]{1,0}") == (512, 2048)
    assert shape_elems_bytes("bf16[10]") == (10, 20)
    assert shape_elems_bytes("(f32[4], s32[2])") == (6, 24)
    assert shape_elems_bytes("pred[]") == (1, 1)
