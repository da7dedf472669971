"""Pallas RWKV6 chunked linear-attention kernel.

Grid = (batch, heads, chunks); chunks is the sequential axis -- the
(D, D) fp32 matrix state lives in VMEM scratch across chunk steps, so
HBM traffic is O(T*D) for activations plus a single (D,D) state
read/write per sequence, not per chunk.  Within a chunk the recurrence
is the parallel form (cumulative per-channel decay + strictly-lower
intra-chunk attention matrix), all MXU matmuls of shape (C,D)x(D,D) /
(C,C)x(C,D).

VMEM: with C=64, D=64: 4 input blocks + att (C,C) + state (D,D) fp32
< 0.5 MB.  TPU-aligned when D=64/128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            s_scr, *, nc, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    r = r_ref[0, 0].astype(jnp.float32)       # (C, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)       # decay in (0,1)
    u = u_ref[0].astype(jnp.float32)          # (D,)
    S = s_scr[...]                            # (D, D)

    logw = jnp.log(w)
    cum = jnp.cumsum(logw, axis=0)
    A_excl = jnp.exp(cum - logw)
    A_incl = jnp.exp(cum)
    A_end = A_incl[-1]                        # (D,)

    rA = r * A_excl
    y = lax.dot_general(rA, S, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    kA = k / jnp.maximum(A_incl, 1e-24)
    att = lax.dot_general(rA, kA, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    ii = lax.broadcasted_iota(jnp.int32, att.shape, 0)
    jj = lax.broadcasted_iota(jnp.int32, att.shape, 1)
    att = jnp.where(ii > jj, att, 0.0)        # strictly lower triangular
    y = y + lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    bonus = jnp.sum(r * (u[None] * k), axis=-1, keepdims=True)
    y = y + bonus * v
    o_ref[0, 0] = y.astype(o_ref.dtype)

    s_scr[...] = A_end[:, None] * S + lax.dot_general(
        kA * A_end[None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _fin():
        sT_ref[0, 0] = s_scr[...]


def rwkv6_scan(r, k, v, w, u, state0, *, chunk=64, interpret=False):
    """r,k,v,w: (B,T,H,D) fp32; u: (H,D); state0: (B,H,D,D) fp32.

    Returns (out (B,T,H,D) fp32, stateT (B,H,D,D))."""
    B, T, H, D = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    tr = lambda a: a.transpose(0, 2, 1, 3)    # (B,H,T,D)

    kern = functools.partial(_kernel, nc=nc, chunk=chunk)
    out, stateT = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, D), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, D), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tr(r), tr(k), tr(v), tr(w), u, state0)
    return out.transpose(0, 2, 1, 3), stateT
