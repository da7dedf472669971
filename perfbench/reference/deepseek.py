"""Plain float32 reference of DeepSeekMoE (``model_type`` ``"deepseek"``,
as in deepseek-moe-16b-base's config.json; arXiv:2401.06066).

Llama's pre-norm decoder (``llama.py``: its attention, RMSNorm, SwiGLU
and layer loop), in which every layer from ``first_k_dense_replace`` on
replaces the dense feed-forward by experts:

  out = h + sum_{e in topk} g_e * SwiGLU_e(n) + SwiGLU_shared(n),
        n = RMSNorm(h)

  g = softmax(n @ router) over ``n_routed_experts``, kept at the
  ``num_experts_per_tok`` largest; with ``norm_topk_prob`` the kept
  gates are divided by their sum, without it they stay the router's
  probabilities.  The shared experts are one SwiGLU of width
  ``n_shared_experts * moe_intermediate_size``.

A configuration with routed experts states ``norm_topk_prob``: the
published DeepSeekMoE config says false, and no default is guessed.
Without ``n_routed_experts`` every layer is dense and this is
``llama.py``'s model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import llama as L

BF16, F32 = L.BF16, L.F32
CHUNK = 256          # tokens per expert-layer block (bounds its temporaries)

global_leaves = L.global_leaves
paged_attention = L.paged_attention


def dims(conf: dict) -> dict:
    return {**L.dims(conf), "E": conf.get("n_routed_experts", 0),
            "k": conf.get("num_experts_per_tok", 0),
            "dx": conf.get("moe_intermediate_size", 0),
            "S": conf.get("n_shared_experts", 0)}


def layer_kinds(conf: dict) -> list[str]:
    """``"dense"`` or ``"moe"`` per layer, in order."""
    n = conf["num_hidden_layers"]
    if "n_routed_experts" not in conf:
        return ["dense"] * n
    if "norm_topk_prob" not in conf:
        raise KeyError("a configuration with routed experts states "
                       "norm_topk_prob (DeepSeekMoE publishes false)")
    k = conf.get("first_k_dense_replace", 0)
    return ["dense"] * k + ["moe"] * (n - k)


def layer_leaves(conf: dict, kind: str) -> dict:
    """name -> (shape, dtype, std) of one layer; std None means ones."""
    if kind == "dense":
        return L.layer_leaves(conf, kind)
    m = dims(conf)
    d, E, dx, sx = m["d"], m["E"], m["dx"], m["S"] * m["dx"]
    out = {
        **L.attention_leaves(conf),
        "router": ((d, E), F32, d ** -0.5),
        "e_gate": ((E, d, dx), BF16, d ** -0.5),
        "e_up": ((E, d, dx), BF16, d ** -0.5),
        "e_down": ((E, dx, d), BF16, dx ** -0.5),
    }
    if sx:
        out.update(L.swiglu_leaves(d, sx, "s"))
    return out


def token_ops(conf: dict, ctx: int, head: bool) -> float:
    """Routed top-k experts, shared experts and the router only, whatever
    the program runs."""
    m = dims(conf)
    d = m["d"]

    def ffn(kind):
        if kind == "dense":
            return 6.0 * d * m["ff"]
        return 6.0 * d * m["dx"] * (m["k"] + m["S"]) + 2.0 * d * m["E"]
    return L.count_ops(conf, ctx, head, layer_kinds(conf), ffn)


def gates(h, router, conf, quant):
    """(T, E) weight of each routed expert for each row of ``h``: the
    router's probabilities at its top-k experts (renormalised there with
    ``norm_topk_prob``), zero elsewhere."""
    m = dims(conf)
    logits = L.mm("td,de->te", h, router, quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, m["k"])
    if conf["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], ids].set(top)


def experts(h, w, conf, quant):
    """h: (T, d).  Routed top-k experts + shared experts."""
    g_all = gates(h, w["router"], conf, quant)

    def block(args):
        hb, gb = args
        g = L.mm("td,edf->etf", hb, w["e_gate"], quant)
        u = L.mm("td,edf->etf", hb, w["e_up"], quant)
        y = L.mm("etf,efd->etd", jax.nn.silu(g) * u, w["e_down"], quant)
        return jnp.einsum("te,etd->td", gb, y,
                          precision=jax.lax.Precision.HIGHEST)

    T = h.shape[0]
    n = -(-T // CHUNK)
    pad = n * CHUNK - T
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, CHUNK, -1)
    gp = jnp.pad(g_all, ((0, pad), (0, 0))).reshape(n, CHUNK, -1)
    out = jax.lax.map(block, (hp, gp)).reshape(n * CHUNK, -1)[:T]
    if "s_gate" in w:
        out = out + L.swiglu(h, w["s_gate"], w["s_up"], w["s_down"], quant)
    return out


def mlp(h, w, conf, kind, quant):
    if kind == "dense":
        return L.dense_mlp(h, w, conf, kind, quant)
    return experts(h, w, conf, quant)


def logits_at(conf: dict, seed: int, seqs: list, rows: list,
              quant: str | None = None) -> list:
    """Float32 logits of each sequence at the positions ``rows[i]``."""
    return L.forward(conf, seed, seqs, rows, quant, kinds=layer_kinds(conf),
                     leaves=functools.partial(layer_leaves, conf), mlp=mlp)
