"""Plain float32 reference of the benchmark's decoder models.

Llama-style pre-norm decoder (DeepSeek LLM 7B) and its DeepSeekMoE
variant, written from the published descriptions in straightforward
``jax.numpy``: no kernels, no cache, no batching tricks.  Weights come
from ``weights.py`` by seed, one layer at a time, so the reference fits
beside nothing else on the chip; it imports nothing of the program.

Semantics:
  h   = x + Attn(RMSNorm(x))      causal MHA/GQA, RoPE (rotate-half,
                                  theta from the config), scale Dh^-0.5
  out = h + FFN(RMSNorm(h))       SwiGLU; or, in MoE layers, softmax
                                  router over n_routed_experts, top-k
                                  experts (SwiGLU each) weighted by their
                                  router probabilities, plus the shared
                                  experts (one SwiGLU of width S * dx)
  logits = RMSNorm(x_L) @ lm_head

One departure, following the program: DeepSeekMoE's published config
has ``norm_topk_prob: false``; the program renormalizes the top-k
weights to sum to one, and so does this reference.

``quant`` computes every matrix product in a lower precision (the
control): ``"fp8"`` rounds both operands to float8_e4m3 with a
per-tensor scale before multiplying.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F32 = jnp.float32
CHUNK = 256          # tokens per expert-layer block (bounds its temporaries)
PAD = 256            # sequences are padded at the end to a multiple of this


def _q(x, quant):
    if quant is None:
        return x.astype(F32)
    if quant == "fp8":
        s = jnp.max(jnp.abs(x)).astype(F32) / 448.0 + 1e-30
        return (x.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown quant {quant!r}")


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """x: (L, H, D) at positions 0..L-1; rotate-half convention."""
    L, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(L, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, conf, quant):
    """h: (L, d) normed rows of one sequence."""
    m = W.dims(conf)
    theta = float(conf.get("rope_theta", 10000.0))
    q = rope(_mm("ld,dhk->lhk", h, w["wq"], quant), theta)
    k = rope(_mm("ld,dhk->lhk", h, w["wk"], quant), theta)
    v = _mm("ld,dhk->lhk", h, w["wv"], quant)
    G = m["H"] // m["KV"]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = _mm("qhk,shk->hqs", q, k, quant) * m["Dh"] ** -0.5
    L = h.shape[0]
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("hqs,shk->qhk", p, v, quant)
    return _mm("qhk,hkd->qd", o, w["wo"], quant)


def swiglu(h, wg, wu, wd, quant):
    g = _mm("td,df->tf", h, wg, quant)
    u = _mm("td,df->tf", h, wu, quant)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, wd, quant)


def experts(h, w, conf, quant):
    """h: (T, d).  Routed top-k experts + shared experts."""
    m = W.dims(conf)
    logits = _mm("td,de->te", h, w["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, m["k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], ids].set(top)     # (T, E)

    def block(args):
        hb, gb = args
        g = _mm("td,edf->etf", hb, w["e_gate"], quant)
        u = _mm("td,edf->etf", hb, w["e_up"], quant)
        y = _mm("etf,efd->etd", jax.nn.silu(g) * u, w["e_down"], quant)
        return jnp.einsum("te,etd->td", gb, y,
                          precision=jax.lax.Precision.HIGHEST)

    T = h.shape[0]
    n = -(-T // CHUNK)
    pad = n * CHUNK - T
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, CHUNK, -1)
    gp = jnp.pad(gates, ((0, pad), (0, 0))).reshape(n, CHUNK, -1)
    out = jax.lax.map(block, (hp, gp)).reshape(n * CHUNK, -1)[:T]
    if "s_gate" in w:
        out = out + swiglu(h, w["s_gate"], w["s_up"], w["s_down"], quant)
    return out


@functools.partial(jax.jit, static_argnames=("kind", "conf_items", "quant"))
def _layer(x, w, *, kind, conf_items, quant):
    """x: (B, L, d) float32 residual streams of B padded sequences."""
    conf = dict(conf_items)
    eps = float(conf.get("rms_norm_eps", 1e-6))

    def one(xs):
        h = rmsnorm(xs, w["attn_norm"], eps)
        xs = xs + attention(h, w, conf, quant)
        h = rmsnorm(xs, w["mlp_norm"], eps)
        if kind == "dense":
            return xs + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)
        return xs + experts(h, w, conf, quant)

    return jax.lax.map(one, x)


def _items(conf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_at(conf: dict, seed: int, seqs: list, rows: list,
              quant: str | None = None) -> list[np.ndarray]:
    """Float32 logits of each sequence at the positions ``rows[i]``.

    ``seqs[i]`` is a token array; every sequence is run whole (causal),
    padded at the end past the longest, layer by layer with the layer's
    weights drawn from ``seed`` and dropped before the next."""
    key = W.seed_key(seed)
    # one padded length for samples of like lengths: one compiled layer
    L = -(-max(len(s) for s in seqs) // PAD) * PAD
    toks = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    g = W.global_weights(key, conf)
    x = jnp.take(g["embed"], jnp.asarray(toks), axis=0).astype(F32)
    head, fnorm = g["lm_head"], g["final_norm"]
    del g
    kinds = W.layer_kinds(conf)
    items = _items(conf)
    with jax.default_matmul_precision("highest"):
        for layer, kind in enumerate(kinds):
            w = W.layer_weights(key, conf, layer)
            x = _layer(x, w, kind=kind, conf_items=items, quant=quant)
            del w
        out = []
        eps = float(conf.get("rms_norm_eps", 1e-6))
        for i, r in enumerate(rows):
            h = rmsnorm(x[i, jnp.asarray(r, np.int32)], fnorm, eps)
            out.append(np.asarray(_mm("td,dv->tv", h, head, quant)))
    return out
