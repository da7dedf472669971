"""Pallas speculative-decoding acceptance kernel.

Computes the Leviathan-et-al. rejection rule entirely on-device: given
gamma draft proposals with draft/target distributions and pre-drawn
uniforms, emit (n_accepted, residual resample distribution).  The token
gathers are expressed as one-hot reductions (gather-free -- TPU-friendly
for the (gamma, V) block sizes of serving, V up to ~256k in one VMEM
block per gamma row at fp32... blocked over V when larger).

Sampling from the residual happens outside (jax.random.categorical) so
kernel and oracle are bit-comparable given the same uniforms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tok_ref, u_ref, dp_ref, tp_ref, n_ref, dist_ref, *, g):
    """Every value stays 2-D ((rows, V) or (rows, 1)): Mosaic reads only
    scalars from SMEM and has no cumprod, so each row's draft token and
    uniform are broadcast in from SMEM scalars, and the accepted prefix
    length is the first rejected row, found with a min reduction."""
    dp = dp_ref[...]                                # (g, V)
    tp = tp_ref[...]                                # (g+1, V)
    V = dp.shape[-1]
    rows = lax.broadcasted_iota(jnp.int32, (g, V), 0)
    col = lax.broadcasted_iota(jnp.int32, (g, 1), 0)
    toks = jnp.zeros((g, V), jnp.int32)
    u = jnp.zeros((g, 1), jnp.float32)
    for i in range(g):
        toks = jnp.where(rows == i, tok_ref[0, i], toks)
        u = jnp.where(col == i, u_ref[0, i], u)
    vio = lax.broadcasted_iota(jnp.int32, (g, V), 1)
    onehot = (vio == toks).astype(jnp.float32)
    p_tok = jnp.sum(tp[:g] * onehot, axis=-1, keepdims=True)   # (g, 1)
    q_tok = jnp.sum(dp * onehot, axis=-1, keepdims=True)
    ratio = p_tok / jnp.maximum(q_tok, 1e-30)
    rejected = u >= jnp.minimum(ratio, 1.0)
    # prefix length = index of the first rejection (g if none)
    n = jnp.min(jnp.where(rejected, col.astype(jnp.float32), float(g)),
                axis=0, keepdims=True)              # (1, 1) float
    n_ref[...] = n.astype(jnp.int32)
    # residual at the cut: max(tp[n] - dp[min(n, g-1)]*(n<g), 0)
    gio = lax.broadcasted_iota(jnp.int32, (g + 1, V), 0).astype(jnp.float32)
    tp_n = jnp.sum(jnp.where(gio == n, tp, 0.0), axis=0, keepdims=True)
    dp_n = jnp.sum(jnp.where(rows.astype(jnp.float32)
                             == jnp.minimum(n, g - 1), dp, 0.0),
                   axis=0, keepdims=True)
    resid = jnp.maximum(tp_n - jnp.where(n < g, 1.0, 0.0) * dp_n, 0.0)
    rs = jnp.sum(resid, axis=-1, keepdims=True)
    dist_ref[...] = jnp.where(rs > 1e-9, resid / jnp.maximum(rs, 1e-30),
                              tp_n)


def spec_accept(draft_tokens, draft_probs, target_probs, u, *,
                interpret=False):
    """Returns (n_accepted (), dist (V,))."""
    g, V = draft_probs.shape
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    n, dist = pl.pallas_call(
        functools.partial(_kernel, g=g),
        grid=(1,),
        in_specs=[
            smem((1, g), lambda i: (0, 0)),
            smem((1, g), lambda i: (0, 0)),
            pl.BlockSpec((g, V), lambda i: (0, 0)),
            pl.BlockSpec((g + 1, V), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, V), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, V), jnp.float32),
        ],
        interpret=interpret,
        name="spec_verify",
    )(draft_tokens.reshape(1, g).astype(jnp.int32),
      u.reshape(1, g).astype(jnp.float32),
      draft_probs.astype(jnp.float32),
      target_probs.astype(jnp.float32))
    return n[0, 0], dist[0]


def spec_verify(draft_tokens, draft_probs, target_probs, rng, *,
                interpret=False):
    """Kernel-backed equivalent of ref.spec_verify_ref."""
    k_u, k_s = jax.random.split(rng)
    g = draft_tokens.shape[0]
    u = jax.random.uniform(k_u, (g,))
    n, dist = spec_accept(draft_tokens, draft_probs, target_probs, u,
                          interpret=interpret)
    nxt = jax.random.categorical(k_s, jnp.log(dist + 1e-30))
    return n.astype(jnp.int32), nxt.astype(jnp.int32)
