"""Pallas TPU flash attention (causal / windowed / full, GQA, softcap).

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
the innermost ("arbitrary") axis so the fp32 online-softmax accumulators
live in VMEM scratch across kv steps.  Block shapes keep the working set
in VMEM: q (Bq, D) + k/v (Bk, D) + acc (Bq, D) fp32 + scores (Bq, Bk)
fp32 -- with Bq=Bk=512, D=128: ~2.4 MB, comfortably under the ~16 MB/core
v5e budget, and all matmul dims are multiples of 128 for the MXU.

Causal/window blocks fully outside the band are *skipped* (pl.when), so
FLOPs match the exact-causal CPU path (models/attention.flash_causal).
GQA maps query head h to kv head h*KV//H in the BlockSpec index maps --
no KV replication in VMEM.

Any length is accepted: a sequence longer than its block is zero-padded
up to a block multiple, padded keys are masked out (``kpos < kv_len``)
and padded query rows are sliced off the result.

Validated in interpret mode against kernels/ref.py on CPU
(tests/test_kernels.py sweeps shapes & dtypes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, softcap, block_q, block_k, nk, kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_start = qi * block_q
    k_start = ki * block_k
    # band test: any (q, k) pair in this block pair can interact?
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live,
                               k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (Bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (Bk, D)
        v = v_ref[0, 0]                              # (Bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len                         # padded keys
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=512, block_k=512, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Skv, KV, D).  Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq, nk = -(-Sq // block_q), -(-Skv // block_k)
    scale = D ** -0.5

    # (B, S, H, D) -> (B, H, S, D) so the lane dim is D; sequences
    # padded up to a whole number of blocks
    def blocked(x, n):
        x = x.transpose(0, 2, 1, 3)
        return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))

    qt = blocked(q, nq * block_q)
    kt = blocked(k, nk * block_k)
    vt = blocked(v, nk * block_k)

    grid = (B, H, nq, nk)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, nk=nk,
        kv_len=Skv)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, KV=KV, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, KV=KV, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m (lane-bcast)
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
