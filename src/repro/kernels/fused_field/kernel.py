"""Pallas kernel: fused ternary field macros (TFOR_ALL_F_OP_F_OP_F).

The paper's hottest offloaded loops are elementwise field expressions fired
hundreds of times per time-step (listing 4, Fig 3). Unfused, each OP is a
separate pass over HBM; the fused kernel reads each operand once and writes
once — on TPU these loops are VPU/bandwidth-bound, so fusion is the entire
win. BlockSpec tiles the (flattened, lane-padded) field into
``(BLOCK_ROWS, 128)`` VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._call import pallas_call

LANES = 128
BLOCK_ROWS = 256            # 256x128 f32 tile = 128 KiB VMEM per operand


def _axpy_kernel(a_ref, x_ref, y_ref, o_ref):
    # o = y + a*x
    a = a_ref[0, 0]
    o_ref[...] = y_ref[...] + a * x_ref[...]


def _xpay_kernel(a_ref, x_ref, y_ref, o_ref):
    # o = x + a*y
    a = a_ref[0, 0]
    o_ref[...] = x_ref[...] + a * y_ref[...]


def _mul_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] * y_ref[...]


def _axpbypz_kernel(a_ref, b_ref, x_ref, y_ref, z_ref, o_ref):
    # o = z + a*x + b*y   (momentum-corrector shape)
    a = a_ref[0, 0]
    b = b_ref[0, 0]
    o_ref[...] = z_ref[...] + a * x_ref[...] + b * y_ref[...]


def _pad_2d(x):
    """Flatten to (rows, 128) with zero padding; return (x2d, orig_size)."""
    n = x.size
    rows = -(-n // LANES)
    rows_pad = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    flat = jnp.pad(x.reshape(-1), (0, rows_pad * LANES - n))
    return flat.reshape(rows_pad, LANES), n


def _run_elementwise(kernel, scalars, arrays, out_dtype):
    """Common driver: tile arrays, broadcast scalars via SMEM-like (1,1)."""
    x0 = arrays[0]
    tiled, n = zip(*[_pad_2d(a) for a in arrays])
    rows = tiled[0].shape[0]
    grid = (rows // BLOCK_ROWS,)
    block = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    sblock = pl.BlockSpec((1, 1), lambda i: (0, 0))
    in_specs = []
    args = []
    for s in scalars:
        in_specs.append(sblock)
        args.append(jnp.asarray(s, out_dtype).reshape(1, 1))
    for t in tiled:
        in_specs.append(block)
        args.append(t)
    out = pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
    )(*args)
    return out.reshape(-1)[: n[0]].reshape(x0.shape)


def fused_axpy(a, x, y):
    return _run_elementwise(_axpy_kernel, [a], [x, y], x.dtype)


def fused_xpay(a, x, y):
    return _run_elementwise(_xpay_kernel, [a], [x, y], x.dtype)


def fused_mul(x, y):
    return _run_elementwise(_mul_kernel, [], [x, y], x.dtype)


def fused_axpbypz(a, x, b, y, z):
    return _run_elementwise(_axpbypz_kernel, [a, b], [x, y, z], x.dtype)
