"""Plain float32 reference of the Llama architecture (``model_type``
``"llama"``): DeepSeek LLM 7B's dense pre-norm decoder.

Written from the published description in straightforward
``jax.numpy``: no kernels, no cache, no batching tricks.  Weights come
from ``weights.py`` by seed, one layer at a time, so the reference fits
beside nothing else on the chip; it imports nothing of the program.

Semantics:
  h   = x + Attn(RMSNorm(x))      causal MHA/GQA, RoPE (rotate-half,
                                  theta from the config), scale Dh^-0.5
  out = h + SwiGLU(RMSNorm(h))
  logits = RMSNorm(x_L) @ lm_head

Other architectures on this pre-norm skeleton (``deepseek.py``) import
its attention, norm, SwiGLU and layer loop (``forward``), and give their
own leaf table and feed-forward.

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

BF16 = jnp.bfloat16
F32 = jnp.float32
PAD = 256            # sequences are padded at the end to a multiple of this
# The query and key projections carry a gain of sqrt(2), so attention
# scores spread by about 2 and each head attends to a few positions, as
# trained heads do, instead of averaging its context: the served tokens
# then depend on the context they saw.
QK_GAIN = 2 ** 0.5


# -- schema ------------------------------------------------------------------

def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"d": d, "H": H, "KV": conf.get("num_key_value_heads", H),
            "Dh": conf.get("head_dim", d // H),
            "ff": conf["intermediate_size"], "V": conf["vocab_size"]}


def layer_kinds(conf: dict) -> list[str]:
    if "n_routed_experts" in conf:
        raise ValueError("a Llama decoder has no expert layers: a "
                         "DeepSeekMoE configuration is model_type 'deepseek'")
    return ["dense"] * conf["num_hidden_layers"]


def attention_leaves(conf: dict) -> dict:
    """The attention sublayer's leaves and both pre-norm scales."""
    m = dims(conf)
    d, H, KV, Dh = m["d"], m["H"], m["KV"], m["Dh"]
    return {
        "attn_norm": ((d,), F32, None),
        "wq": ((d, H, Dh), BF16, QK_GAIN * d ** -0.5),
        "wk": ((d, KV, Dh), BF16, QK_GAIN * d ** -0.5),
        "wv": ((d, KV, Dh), BF16, d ** -0.5),
        "wo": ((H, Dh, d), BF16, (H * Dh) ** -0.5),
        "mlp_norm": ((d,), F32, None),
    }


def swiglu_leaves(d: int, ff: int, prefix: str) -> dict:
    return {f"{prefix}_gate": ((d, ff), BF16, d ** -0.5),
            f"{prefix}_up": ((d, ff), BF16, d ** -0.5),
            f"{prefix}_down": ((ff, d), BF16, ff ** -0.5)}


def layer_leaves(conf: dict, kind: str) -> dict:
    """name -> (shape, dtype, std) of one layer; std None means ones."""
    m = dims(conf)
    return {**attention_leaves(conf), **swiglu_leaves(m["d"], m["ff"], "w")}


def global_leaves(conf: dict) -> dict:
    m = dims(conf)
    return {"embed": ((m["V"], m["d"]), BF16, 1.0),
            "final_norm": ((m["d"],), F32, None),
            "lm_head": ((m["d"], m["V"]), BF16, m["d"] ** -0.5)}


def paged_attention(conf: dict) -> dict:
    """Layers that attend through the paged KV cache, and their heads."""
    m = dims(conf)
    return {"layers": conf["num_hidden_layers"], "H": m["H"],
            "KV": m["KV"], "Dh": m["Dh"]}


# -- operation counts --------------------------------------------------------

def count_ops(conf: dict, ctx: int, head: bool, kinds: list[str],
              ffn_ops) -> float:
    """Operations one token needs at a position attending ``ctx`` keys:
    each layer's projections and attention, ``ffn_ops(kind)`` for its
    feed-forward, and the output head where its logits are needed."""
    m = dims(conf)
    d, H, KV, D = m["d"], m["H"], m["KV"], m["Dh"]
    total = 0.0
    for kind in kinds:
        total += 2.0 * d * (H + 2 * KV) * D + 2.0 * H * D * d
        total += 4.0 * H * D * ctx
        total += ffn_ops(kind)
    if head:
        total += 2.0 * d * m["V"]
    return total


def token_ops(conf: dict, ctx: int, head: bool) -> float:
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    return count_ops(conf, ctx, head, layer_kinds(conf),
                     lambda kind: 6.0 * d * ff)


# -- forward -----------------------------------------------------------------

def _q(x, quant):
    if quant is None:
        return x.astype(F32)
    if quant == "fp8":
        s = jnp.max(jnp.abs(x)).astype(F32) / 448.0 + 1e-30
        return (x.astype(F32) / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown quant {quant!r}")


def mm(spec, a, b, quant):
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
    m = dims(conf)
    theta = float(conf.get("rope_theta", 10000.0))
    q = rope(mm("ld,dhk->lhk", h, w["wq"], quant), theta)
    k = rope(mm("ld,dhk->lhk", h, w["wk"], quant), theta)
    v = mm("ld,dhk->lhk", h, w["wv"], quant)
    G = m["H"] // m["KV"]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = mm("qhk,shk->hqs", q, k, quant) * m["Dh"] ** -0.5
    L = h.shape[0]
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("hqs,shk->qhk", p, v, quant)
    return mm("qhk,hkd->qd", o, w["wo"], quant)


def swiglu(h, wg, wu, wd, quant):
    g = mm("td,df->tf", h, wg, quant)
    u = mm("td,df->tf", h, wu, quant)
    return mm("tf,fd->td", jax.nn.silu(g) * u, wd, quant)


def dense_mlp(h, w, conf, kind, quant):
    return swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)


@functools.partial(jax.jit,
                   static_argnames=("mlp", "kind", "conf_items", "quant"))
def _layer(x, w, *, mlp, kind, conf_items, quant):
    """x: (B, L, d) float32 residual streams of B padded sequences."""
    conf = dict(conf_items)
    eps = float(conf.get("rms_norm_eps", 1e-6))

    def one(xs):
        h = rmsnorm(xs, w["attn_norm"], eps)
        xs = xs + attention(h, w, conf, quant)
        h = rmsnorm(xs, w["mlp_norm"], eps)
        return xs + mlp(h, w, conf, kind, quant)

    return jax.lax.map(one, x)


def _items(conf: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in conf.items()
                        if isinstance(v, (int, float, str, bool))))


def forward(conf: dict, seed: int, seqs: list, rows: list, quant, *,
            kinds: list[str], leaves, mlp) -> list[np.ndarray]:
    """Float32 logits of each sequence at the positions ``rows[i]``, on
    the pre-norm skeleton: ``kinds`` per layer, ``leaves(kind)`` the leaf
    table of a layer of that kind, ``mlp(h, w, conf, kind, quant)`` its
    feed-forward.

    ``seqs[i]`` is a token array; every sequence is run whole (causal),
    padded at the end past the longest, layer by layer with the layer's
    weights drawn from ``seed`` and dropped before the next."""
    key = W.seed_key(seed)
    # one padded length for samples of like lengths: one compiled layer
    L = -(-max(len(s) for s in seqs) // PAD) * PAD
    toks = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    g = W.global_weights(key, global_leaves(conf))
    x = jnp.take(g["embed"], jnp.asarray(toks), axis=0).astype(F32)
    head, fnorm = g["lm_head"], g["final_norm"]
    del g
    items = _items(conf)
    with jax.default_matmul_precision("highest"):
        for layer, kind in enumerate(kinds):
            w = W.layer_weights(key, leaves(kind), layer)
            x = _layer(x, w, mlp=mlp, kind=kind, conf_items=items,
                       quant=quant)
            del w
        out = []
        eps = float(conf.get("rms_norm_eps", 1e-6))
        for i, r in enumerate(rows):
            h = rmsnorm(x[i, jnp.asarray(r, np.int32)], fnorm, eps)
            out.append(np.asarray(mm("td,dv->tv", h, head, quant)))
    return out


def logits_at(conf: dict, seed: int, seqs: list, rows: list,
              quant: str | None = None) -> list[np.ndarray]:
    """Float32 logits of each sequence at the positions ``rows[i]``."""
    return forward(conf, seed, seqs, rows, quant, kinds=layer_kinds(conf),
                   leaves=functools.partial(layer_leaves, conf),
                   mlp=dense_mlp)
