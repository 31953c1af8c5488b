"""GPipe pipeline parallelism == sequential execution (4-stage subprocess)."""
import subprocess
import sys

CODE = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_smoke_mesh
from repro.runtime.pipeline import gpipe_apply, split_microbatches

mesh = make_smoke_mesh((4,), ("pod",))
S, d = 4, 8
ws = jnp.asarray(np.random.RandomState(1).randn(S, d, d) * 0.3, jnp.float32)
def stage(w, x): return jnp.tanh(x @ w)
x = jnp.asarray(np.random.RandomState(2).randn(16, d), jnp.float32)
y = gpipe_apply(stage, ws, split_microbatches(x, 8), mesh, axis="pod")
ref = x
for s in range(S):
    ref = stage(ws[s], ref)
np.testing.assert_allclose(np.asarray(y).reshape(16, d), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)

# differentiability (PP backward schedule via AD)
def loss(ws, x):
    y = gpipe_apply(stage, ws, split_microbatches(x, 4), mesh, axis="pod")
    return jnp.sum(y ** 2)
g = jax.grad(loss)(ws, x)
def loss_ref(ws, x):
    r = x
    for s in range(S): r = stage(ws[s], r)
    return jnp.sum(r ** 2)
g_ref = jax.grad(loss_ref)(ws, x)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)
print("GPIPE_OK")
'''


def test_gpipe_subprocess():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=300)
    assert "GPIPE_OK" in r.stdout, r.stderr[-2000:]
