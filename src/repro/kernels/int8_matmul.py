"""Pallas int8-weight matmul for edge-tier replicas.

Weights are stored int8 with per-output-channel fp32 scales (half the
HBM traffic of bf16 -- decode on the edge tier is HBM-bound, so this is
a direct ~2x decode-latency win; see bench_replication quality/latency
trade).  Grid = (M/bm, N/bn, K/bk) with K innermost; fp32 accumulator in
VMEM scratch; scales applied once on the final K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    x = x_ref[...]
    w = w_ref[...]
    acc_scr[...] += lax.dot_general(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[...] = (acc_scr[...] * s_ref[0][None, :]).astype(o_ref.dtype)


def int8_matmul(x, w_q, w_scale, *, block_m=256, block_n=256, block_k=512,
                interpret=False):
    """x: (..., K) bf16/f32; w_q: (K, N) int8; w_scale: (N,) f32."""
    orig_shape = x.shape
    K, N = w_q.shape
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm, bn, bk = (min(block_m, M), min(block_n, N), min(block_k, K))
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)

    kern = functools.partial(_kernel, nk=grid[2])
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2, w_q, w_scale.reshape(1, N))
    return out.reshape(*orig_shape[:-1], N)
