"""Pallas kernel: RWKV6 chunked linear-attention scan.

The perf-critical mixer of the rwkv6-7b assigned arch. Grid is
(batch*heads, T/C) with the chunk axis sequential ("arbitrary" semantics on
TPU): the [hd, hd] fp32 state lives in a VMEM scratch and is carried across
chunk steps — one HBM read of (r,k,v,logw) and one write of the output per
token, instead of the pure-JAX path's scan-carried HBM state round-trips.

Math is identical to ``repro.models.rwkv6.rwkv_chunk`` (the anchor
semantics; ``ref.py`` re-exports the sequential oracle): all decay exponents
are cumulative differences with t >= i, so everything stays <= 0 — no
overflow, no rescaling pass needed (the log-space-safety argument in
rwkv6.py applies unchanged inside the kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._call import pallas_call

CHUNK = 64


def _kernel(C, hd, r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_out_ref,
            state_ref):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros((hd, hd), jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # [C, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # [1, hd] -> broadcast
    S = state_ref[...]

    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (cumsum has no Mosaic lowering)
    tril = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(jnp.float32)
    la = jnp.dot(tril, lw, preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)   # [C, hd]
    la_prev = la - lw
    rA = r * jnp.exp(la_prev)
    inter = rA @ S                             # [C, hd_v]

    # intra-chunk: att[t,i] = sum_d r[t,d] k[i,d] exp(la_prev[t,d]-la[i,d])
    D = la_prev[:, None, :] - la[None, :, :]   # [C, C, hd] (<= 0 for t > i)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) >
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    D = jnp.where(mask[:, :, None], D, -jnp.inf)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(D), axis=-1)
    diag = jnp.sum(r * k * u, axis=-1)         # u-bonus for i == t
    att = att + jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) ==
        jax.lax.broadcasted_iota(jnp.int32, (C, C), 1), diag[:, None], 0.0)
    intra = att @ v
    o_ref[0] = (inter + intra).astype(o_ref.dtype)

    la_C = la[-1]                              # [hd]
    kA = k * jnp.exp(la_C[None, :] - la)
    state_ref[...] = jnp.exp(la_C)[:, None] * S + kA.T @ v

    @pl.when(ci == nc - 1)
    def _flush():
        s_out_ref[0] = state_ref[...]


def rwkv6_scan(r, k, v, logw, u, chunk: int = CHUNK):
    """r,k,v,logw [B,T,H,hd]; u [H,hd]. Returns (out [B,T,H,hd] f32,
    S_final [B,H,hd,hd] f32). Zero initial state (prefill semantics)."""
    B, T, H, hd = r.shape
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    nc = T // C

    def bh(x):     # [B,T,H,hd] -> [B*H, T, hd]
        return jnp.moveaxis(x, 2, 1).reshape(B * H, T, hd)

    rb, kb, vb, lwb = bh(r), bh(k), bh(v), bh(logw)
    ub = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)

    io_spec = pl.BlockSpec((1, C, hd), lambda b, c: (b, c, 0))
    u_spec = pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0))
    out, s_final = pallas_call(
        functools.partial(_kernel, C, hd),
        grid=(B * H, nc),
        in_specs=[io_spec, io_spec, io_spec, io_spec, u_spec],
        out_specs=[io_spec,
                   pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
    )(rb, kb, vb, lwb, ub)
    out = jnp.moveaxis(out.reshape(B, H, T, hd), 1, 2)
    return out, s_final.reshape(B, H, hd, hd)
