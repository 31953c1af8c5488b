"""Pallas kernel: 7-point DIA stencil SpMV (OpenFOAM lduMatrix::Amul on TPU).

TPU adaptation (docs/DESIGN.md §2): the unstructured LDU face-list gather/scatter
becomes, on a structured grid, y[i] = d[i]*x[i] + sum_f off[f][i]*x[i+s_f]
with six constant strides s_f in the flattened index space.

Layout: every flat field is viewed as ``(rows, 128)`` lanes and processed
in chunks of ``ROWS`` rows. Pointwise operands (diagonal, coefficients,
centre values) arrive as ``(ROWS, 128)`` VMEM blocks through BlockSpecs.
The shifted operand stays in HBM, zero-padded by whole rows, and each grid
step DMAs the row windows it needs into VMEM scratch: a flat shift
``s = 128*q + r`` reads rows ``q`` and ``q+1`` of the chunk's neighbourhood
and joins them with one lane rotation (:func:`_shifted`). Every DMA starts
on a row boundary and every vector op is on whole ``(ROWS, 128)`` tiles,
so no slice is unaligned.

HBM traffic per call, which is not one pass:

- the zero-padded copy of the shifted operand (:func:`_halo_tiles`) is
  written by XLA on every call, one read and one write of the field;
- the kernel then reads that copy once per window row offset: 5 windows
  per chunk at 128^3, 7 at 100^3 (:func:`_window_rows`);
- the pointwise operands and the coefficients are read once and ``y``
  written once; where the cell count is not a multiple of ``ROWS*128``
  (100^3), :func:`_tiles` pads, i.e. copies, each of them first;
- the window DMAs are synchronous: each grid step starts its copies,
  waits for all of them, then computes, with no double buffering, so
  HBM reads and compute do not overlap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._call import pallas_call

LANES = 128
ROWS = 256                     # rows per grid step (multiple of 8)


def _strides(shape3):
    nx, ny, nz = shape3
    return (-ny * nz, ny * nz, -nz, nz, -1, 1)   # (-x,+x,-y,+y,-z,+z)


def _window_rows(strides):
    """Row offsets of the HBM windows the shifted reads need."""
    rows = {0}
    for s in strides:
        q, r = divmod(s, LANES)
        rows.add(q)
        if r:
            rows.add(q + 1)
    return tuple(sorted(rows))


@functools.lru_cache(maxsize=None)
def _layout(shape3):
    """(rows per chunk, data rows, window row offsets, leading pad rows,
    trailing pad rows) of a grid's flat field."""
    n = shape3[0] * shape3[1] * shape3[2]
    rows_needed = -(-n // LANES)
    rc = min(ROWS, -(-rows_needed // 8) * 8)
    rows = -(-rows_needed // rc) * rc
    wins = _window_rows(_strides(shape3))
    return rc, rows, wins, -wins[0], wins[-1]


def _tiles(x, rows):
    """Flat field -> zero-padded ``(rows, 128)`` tiles."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, rows * LANES - flat.size)).reshape(rows, LANES)


def _halo_tiles(x, rows, lo, hi):
    """Flat field -> ``(lo + rows + hi, 128)`` tiles with zero halo rows."""
    return jnp.pad(_tiles(x, rows), ((lo, hi), (0, 0)))


def _fetch(src_hbm, bufs, sem, rc, wins, lo):
    """DMA this chunk's window rows (one copy per row offset) from HBM
    into VMEM scratch; returns ``{row offset: VMEM ref}``."""
    start = pl.program_id(0) * rc + lo
    copies = [pltpu.make_async_copy(src_hbm.at[pl.ds(start + q, rc)],
                                    buf, sem.at[j])
              for j, (q, buf) in enumerate(zip(wins, bufs))]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    return dict(zip(wins, bufs))


def _shifted(win, s, rc):
    """x[p + s] for the chunk's cells p, from the fetched windows."""
    q, r = divmod(s, LANES)
    if r == 0:
        return win[q][...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rc, LANES), 1)
    joined = jnp.where(lane >= r, win[q][...], win[q + 1][...])
    return pltpu.roll(joined, LANES - r, 1)


def _nbsum(off_ref, win, strides, rc):
    """sum_f off[f] * x[p + s_f] over the six neighbours."""
    acc = jnp.zeros((rc, LANES), jnp.float32)
    for f, s in enumerate(strides):
        acc = acc + off_ref[f] * _shifted(win, s, rc)
    return acc


def _run(kernel, shape3, dtype, blocks, off, src):
    """Run ``kernel`` over the chunks of a grid: the pointwise ``blocks``
    and the ``(6, ROWS, 128)`` coefficient block arrive through
    BlockSpecs, the shifted operand ``src`` stays in HBM with zero halo
    rows for :func:`_fetch`."""
    rc, rows, wins, lo, hi = _layout(shape3)
    strides = _strides(shape3)
    block = pl.BlockSpec((rc, LANES), lambda i: (i, 0))
    offs = jnp.pad(off.reshape(6, -1),
                   ((0, 0), (0, rows * LANES - off[0].size)))
    out = pallas_call(
        functools.partial(kernel, strides, rc, wins, lo),
        grid=(rows // rc,),
        in_specs=[block] * len(blocks) + [
            pl.BlockSpec((6, rc, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        scratch_shapes=[pltpu.VMEM((rc, LANES), dtype)] * len(wins)
        + [pltpu.SemaphoreType.DMA((len(wins),))],
    )(*[_tiles(b, rows) for b in blocks], offs.reshape(6, rows, LANES),
      _halo_tiles(src, rows, lo, hi))
    n = shape3[0] * shape3[1] * shape3[2]
    return out.reshape(-1)[:n].reshape(shape3)


def _amul_kernel(strides, rc, wins, lo, diag_ref, off_ref, x_hbm, y_ref,
                 *scratch):
    win = _fetch(x_hbm, scratch[:-1], scratch[-1], rc, wins, lo)
    acc = diag_ref[...] * win[0][...]
    for f, s in enumerate(strides):
        acc = acc + off_ref[f] * _shifted(win, s, rc)
    y_ref[...] = acc


def stencil_spmv(diag, off, x):
    """diag [nx,ny,nz]; off [6,nx,ny,nz]; x [nx,ny,nz] -> y = A x."""
    return _run(_amul_kernel, diag.shape, x.dtype, [diag], off, x)


def _rb_kernel(strides, rc, wins, lo, red_ref, r_ref, rd_ref, off_ref,
               yr_hbm, w_ref, *scratch):
    """Forward half-sweep of the two-color DILU: red cells take
    ``y_r = r * rd``, black cells ``y_b = (r - sum L_br y_r) * rd``."""
    win = _fetch(yr_hbm, scratch[:-1], scratch[-1], rc, wins, lo)
    acc = _nbsum(off_ref, win, strides, rc)
    blk = 1.0 - red_ref[...]
    w_ref[...] = win[0][...] + blk * (r_ref[...] - acc) * rd_ref[...]


def _rb_back_kernel(strides, rc, wins, lo, red_ref, y_ref, rd_ref, off_ref,
                    yb_hbm, w_ref, *scratch):
    """Backward half-sweep: z_b = y_b ; z_r = y_r - rd * sum U_rb y_b."""
    win = _fetch(yb_hbm, scratch[:-1], scratch[-1], rc, wins, lo)
    acc = _nbsum(off_ref, win, strides, rc)
    red = red_ref[...]
    y = y_ref[...]
    w_ref[...] = red * (y - rd_ref[...] * acc) + (1.0 - red) * y


def rb_dilu_forward(rdiag, red, off, r):
    """Forward half-sweep of the two-color DILU (see precond.py); the red
    values ``y_r`` every black cell reads are formed once, outside."""
    red = red.astype(r.dtype)
    y_r = red * r * rdiag
    return _run(_rb_kernel, r.shape, r.dtype, [red, r, rdiag], off, y_r)


def rb_dilu_backward(rdiag, red, off, y):
    red = red.astype(y.dtype)
    y_b = (1.0 - red) * y
    return _run(_rb_back_kernel, y.shape, y.dtype, [red, y, rdiag], off, y_b)


def rb_dilu(rdiag, red, off, r):
    """Full preconditioner apply: the forward->backward half-sweep
    composition, defined ONCE here — ops.py jits it and the application
    regions (precond/solvers) register it as their pallas variant."""
    return rb_dilu_backward(rdiag, red, off,
                            rb_dilu_forward(rdiag, red, off, r))
