"""Pallas flash-decode: one-token attention against a (ring-buffered)
KV cache, GQA-aware, and against a paged KV pool.

Dense cache (``decode_attention``).  Grid = (batch, kv_heads,
kv_blocks): all G query heads of a KV group are processed as one (G, D)
block, so each KV tile is loaded from HBM once per group (not once per
query head) and the score matmul is (G x D) @ (D x Bk) -- MXU-shaped
even at decode.  kv_blocks is the innermost "arbitrary" axis; fp32
online-softmax accumulators persist in VMEM scratch (flash-decode
split-K).  Validity masking comes from the cache's ``abs_pos`` slot map
(supports ring-buffered sliding-window caches and partially-filled
caches in one rule); for global caches (window=0) blocks entirely beyond
the current position are skipped.

Paged pool (``paged_decode_attention``).  The pools stay in HBM in their
own (P, page_size, KV, D) layout, untransposed: one page holds every KV
head of page_size tokens contiguously, and the kernel copies whole pages
into VMEM with its own async copies.  Grid = (batch rows, head groups).
Each grid step walks its row's live span -- the mapped pages at or
before the row's position, and inside its window where there is one --
a block of ``ppb`` pages at a time, with two blocks in flight: block
i+1's copies start before block i is attended.  Only the mapped pages
of the span are copied and attended; a row that attends no page (an
inactive row, all -1) costs one grid step and no copy, and returns
zeros.  Scores are worked out on the vector unit in the pages' own
layout, every head of a page at once (one query token per row gives
the MXU nothing to fill), with the fp32 online softmax updated page by
page.  The block follows the shapes (``_paged_blocking``): ``ppb`` pages
make about 2 MB of K and V, and where one page of every head is larger
than that the heads split into groups, a second grid axis.  The
flattened page table stays the kernel's first operand, ahead of the
positions and each row's live span.
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


_BLOCK_BYTES = 2 << 20     # K+V bytes one page block brings into VMEM


def _paged_blocking(ps, KV, D, itemsize, NP):
    """(pages per block, kv heads per group) for a pool of pages of
    (ps, KV, D) elements of ``itemsize`` bytes and NP-page tables.

    A block's K and V come to about ``_BLOCK_BYTES``; with two blocks in
    flight that stays well inside the scoped VMEM.  Where one page of
    every head is already larger, the heads split into groups (a second
    grid axis), halving while a group stays a multiple of the sublane
    tile the head axis is laid out on."""
    sub = 8 * (4 // itemsize)
    kvb = KV
    while (2 * ps * kvb * D * itemsize > _BLOCK_BYTES and kvb % 2 == 0
           and (kvb // 2) % sub == 0):
        kvb //= 2
    ppb = _BLOCK_BYTES // (2 * ps * kvb * D * itemsize)
    return max(1, min(NP, ppb)), kvb


def _paged_kernel(pt_ref, pos_ref, span_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *, scale,
                  window, softcap, page_size, np_pages, ppb, kvb):
    b = pl.program_id(0)
    h0 = pl.program_id(1) * kvb
    G = q_ref.shape[1]
    pos = pos_ref[b]
    first, last = span_ref[2 * b], span_ref[2 * b + 1]

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def page_of(j):
        """(page id, live) of logical page j of this row: live pages lie
        in [first, last] and are mapped."""
        page = pt_ref[b * np_pages + jnp.minimum(j, np_pages - 1)]
        live = (j >= first) & (j <= last) & (page >= 0)
        return jnp.maximum(page, 0), live

    heads = pl.ds(h0, kvb)

    def copies(blk, slot):
        """Each live page of block blk, K and V, into buffer slot."""
        for i in range(ppb):
            page, live = page_of(blk * ppb + i)
            yield live, (
                pltpu.make_async_copy(k_hbm.at[page, :, heads, :],
                                      k_buf.at[slot, i], sems.at[slot]),
                pltpu.make_async_copy(v_hbm.at[page, :, heads, :],
                                      v_buf.at[slot, i], sems.at[slot]))

    def start(blk, slot):
        for live, cps in copies(blk, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.start()

    def wait(blk, slot):
        for live, cps in copies(blk, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.wait()

    def attend(j, slot, i):
        """Online-softmax update of every head over logical page j,
        whose K/V sit at k_buf/v_buf[slot, i]."""
        k = k_buf[slot, i].astype(jnp.float32)       # (ps, kvb, D)
        v = v_buf[slot, i]
        ap = j * page_size + lax.broadcasted_iota(
            jnp.int32, (page_size, 1, 1), 0)         # abs slot of each key
        valid = ap <= pos
        if window:
            valid = jnp.logical_and(valid, ap > pos - window)
        for g in range(G):
            q = q_ref[0, g].astype(jnp.float32) * scale          # (kvb, D)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)     # (ps, kvb, 1)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(valid, s, NEG_INF)
            m_prev, l_prev = m_scr[g], l_scr[g]                  # (kvb, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=0))
            p = jnp.exp(s - m_new[None])
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_prev * corr + p.sum(axis=0)
            pv = p.astype(v.dtype).astype(jnp.float32) * v.astype(jnp.float32)
            acc_scr[g] = acc_scr[g] * corr + pv.sum(axis=0)
            m_scr[g] = m_new

    blk0, blk1 = first // ppb, last // ppb

    @pl.when(first <= last)
    def _walk():
        start(blk0, 0)

        def block(blk, _):
            slot = (blk - blk0) % 2

            @pl.when(blk < blk1)
            def _prefetch():
                start(blk + 1, 1 - slot)

            wait(blk, slot)

            def page(i, _):
                j = blk * ppb + i

                @pl.when(page_of(j)[1])
                def _():
                    attend(j, slot, i)

            lax.fori_loop(0, ppb, page, None)

        lax.fori_loop(blk0, blk1 + 1, block, None)

    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _live_span(page_table, positions, page_size, window):
    """(B, 2) int32: the first and last logical page each row attends,
    mapped and at or before its position (and overlapping its window);
    first > last where there is none."""
    NP = page_table.shape[1]
    j = jnp.arange(NP, dtype=jnp.int32)[None]
    pos = positions[:, None]
    live = (page_table >= 0) & (j * page_size <= pos)
    if window:
        live &= (j + 1) * page_size - 1 > pos - window
    first = jnp.min(jnp.where(live, j, NP), axis=1)
    last = jnp.max(jnp.where(live, j, -1), axis=1)
    return jnp.stack([first, last], axis=1)


def paged_decode_attention(q, k_pool, v_pool, page_table, positions, *,
                           window=0, softcap=0.0, interpret=False):
    """Flash-decode over a paged KV pool.

    q: (B,1,H,D); pools: (P, page_size, KV, D) shared across batch rows;
    page_table: (B, NP) int32 page ids (-1 = unmapped); positions: (B,).
    Returns (B,1,H,D); a row that attends no mapped page returns zeros.
    How the kernel walks the pool: the module docstring.
    """
    B, _, H, D = q.shape
    ps, KV = k_pool.shape[1], k_pool.shape[2]
    G = H // KV
    NP = page_table.shape[1]
    ppb, kvb = _paged_blocking(ps, KV, D, k_pool.dtype.itemsize, NP)

    qt = q.reshape(B, KV, G, D).transpose(0, 2, 1, 3)   # (B, G, KV, D)
    pt_flat = page_table.reshape(B * NP).astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    span = _live_span(page_table, pos, ps, window).reshape(B * 2)

    kern = functools.partial(
        _paged_kernel, scale=D ** -0.5, window=window, softcap=softcap,
        page_size=ps, np_pages=NP, ppb=ppb, kvb=kvb)
    buf = pltpu.VMEM((2, ppb, ps, kvb, D), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV // kvb),
        in_specs=[
            pl.BlockSpec((1, G, kvb, D), lambda b, h, *_: (b, 0, h, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, kvb, D), lambda b, h, *_: (b, 0, h, 0)),
        scratch_shapes=[
            buf, buf, pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((G, kvb, 1), jnp.float32),
            pltpu.VMEM((G, kvb, 1), jnp.float32),
            pltpu.VMEM((G, kvb, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, KV, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="paged_decode_attention",
    )(pt_flat, pos, span, qt, k_pool, v_pool)
    return out.transpose(0, 2, 1, 3).reshape(B, 1, H, D)
