"""Pallas flash-decode: one-token attention against a (ring-buffered)
KV cache, GQA-aware.

Grid = (batch, kv_heads, kv_blocks): all G query heads of a KV group are
processed as one (G, D) block, so each KV tile is loaded from HBM once
per group (not once per query head) and the score matmul is (G x D) @
(D x Bk) -- MXU-shaped even at decode.  kv_blocks is the innermost
"arbitrary" axis; fp32 online-softmax accumulators persist in VMEM
scratch (flash-decode split-K).

Validity masking comes from the cache's ``abs_pos`` slot map (supports
ring-buffered sliding-window caches and partially-filled caches in one
rule); for global caches (window=0) blocks entirely beyond the current
position are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, ap_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, window, softcap,
            block_k, nk, skip_beyond_pos):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[0, 0]
    live = jnp.bool_(True)
    if skip_beyond_pos:
        # global caches fill slots in absolute order: skip empty tail
        live = ki * block_k <= pos

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (Bk, D)
        v = v_ref[0, 0]                              # (Bk, D)
        ap = ap_ref[0]                               # (Bk,) int32
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        valid = jnp.logical_and(ap >= 0, ap <= pos)
        if window:
            valid = jnp.logical_and(valid, ap > pos - window)
        s = jnp.where(valid[None, :], s, NEG_INF)
        m_prev, l_prev = m_scr[:, 0], l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, abs_pos, positions, *,
                     window=0, softcap=0.0, block_k=512, interpret=False):
    """q: (B,1,H,D); caches: (B,Sc,KV,D); abs_pos: (B,Sc);
    positions: (B,).  Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    Sc, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    block_k = min(block_k, Sc)
    assert Sc % block_k == 0
    nk = Sc // block_k
    scale = D ** -0.5

    qt = q.reshape(B, KV, G, D)                       # group-major heads
    kt = k_cache.transpose(0, 2, 1, 3)                # (B, KV, Sc, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    pos2 = positions.reshape(B, 1).astype(jnp.int32)

    kern = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        block_k=block_k, nk=nk, skip_beyond_pos=(window == 0))
    out = pl.pallas_call(
        kern,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, j: (b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, block_k), lambda b, h, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(pos2, qt, kt, vt, abs_pos)
    return out.reshape(B, 1, H, D)


def _paged_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, window, softcap,
                  page_size, np_pages):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[b]
    page = pt_ref[b * np_pages + j]
    # dead page (unmapped / inactive row) or wholly beyond the decode
    # position: skip the block -- the DMA still ran (index_map clamps
    # the page id to 0) but nothing is accumulated
    live = jnp.logical_and(page >= 0, j * page_size <= pos)
    if window:
        live = jnp.logical_and(
            live, j * page_size + page_size - 1 > pos - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (ps, D)
        v = v_ref[0, 0]                              # (ps, D)
        ap = j * page_size + lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)            # (1, ps) abs slots
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        valid = ap <= pos
        if window:
            valid = jnp.logical_and(valid, ap > pos - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[:, 0], l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(j == np_pages - 1)
    def _fin():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_table, positions, *,
                           window=0, softcap=0.0, interpret=False):
    """Flash-decode over a paged KV pool.

    q: (B,1,H,D); pools: (P, page_size, KV, D) shared across batch rows;
    page_table: (B, NP) int32 page ids (-1 = unmapped); positions: (B,).
    Returns (B,1,H,D).

    The page table and positions ride as scalar-prefetch arguments
    (``PrefetchScalarGridSpec``): the k/v index_maps read the page id to
    aim each block DMA at the right pool page, so the kernel never
    materialises a gathered (B, NP*ps, ...) cache.  The inner grid axis
    walks the NP logical pages of one row; dead pages clamp their DMA to
    page 0 and skip accumulation.
    """
    B, _, H, D = q.shape
    ps, KV = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    NP = page_table.shape[1]
    scale = D ** -0.5

    qt = q.reshape(B, KV, G, D)                       # group-major heads
    kt = k_pool.transpose(0, 2, 1, 3)                 # (P, KV, ps, D)
    vt = v_pool.transpose(0, 2, 1, 3)
    pt_flat = page_table.reshape(B * NP).astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    def _kv_map(b, h, j, pt, pv):
        return (jnp.maximum(pt[b * NP + j], 0), h, 0, 0)

    kern = functools.partial(
        _paged_kernel, scale=scale, window=window, softcap=softcap,
        page_size=ps, np_pages=NP)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, NP),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, pt, pv: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D), _kv_map),
            pl.BlockSpec((1, 1, ps, D), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, j, pt, pv: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(pt_flat, pos, qt, kt, vt)
    return out.reshape(B, 1, H, D)
