"""Seeded weights of a configuration, shared by the harness and the reference.

Every weight is a pure function of ``(seed, leaf name, layer)``: the
harness draws a whole model in one jitted call (layers stacked by a vmap
over layer keys), and the reference draws one layer at a time and gets
the same numbers.  Nothing here imports the program under test.

Each matrix is drawn directly in the type it is served in (bf16; the
router in float32), normal with standard deviation 1/sqrt(fan-in), so
that every layer's output has about unit scale; the embedding is unit
normal and norm scales are ones.  The query and key projections carry a
gain of sqrt(2), so attention scores spread by about 2 and each head
attends to a few positions, as trained heads do, instead of averaging
its context: the served tokens then depend on the context they saw.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16
F32 = jnp.float32
QK_GAIN = 2 ** 0.5


def layer_kinds(conf: dict) -> list[str]:
    """``"dense"`` or ``"moe"`` per layer, in order."""
    n = conf["num_hidden_layers"]
    if "n_routed_experts" not in conf:
        return ["dense"] * n
    k = conf.get("first_k_dense_replace", 0)
    return ["dense"] * k + ["moe"] * (n - k)


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {
        "d": d, "H": H, "KV": conf.get("num_key_value_heads", H),
        "Dh": conf.get("head_dim", d // H), "ff": conf["intermediate_size"],
        "V": conf["vocab_size"], "E": conf.get("n_routed_experts", 0),
        "k": conf.get("num_experts_per_tok", 0),
        "dx": conf.get("moe_intermediate_size", 0),
        "S": conf.get("n_shared_experts", 0),
    }


def layer_leaves(conf: dict, kind: str) -> dict:
    """name -> (shape, dtype, std); std None means ones."""
    m = dims(conf)
    d, H, KV, Dh = m["d"], m["H"], m["KV"], m["Dh"]
    out = {
        "attn_norm": ((d,), F32, None),
        "wq": ((d, H, Dh), BF16, QK_GAIN * d ** -0.5),
        "wk": ((d, KV, Dh), BF16, QK_GAIN * d ** -0.5),
        "wv": ((d, KV, Dh), BF16, d ** -0.5),
        "wo": ((H, Dh, d), BF16, (H * Dh) ** -0.5),
        "mlp_norm": ((d,), F32, None),
    }
    if kind == "dense":
        ff = m["ff"]
        out.update({"w_gate": ((d, ff), BF16, d ** -0.5),
                    "w_up": ((d, ff), BF16, d ** -0.5),
                    "w_down": ((ff, d), BF16, ff ** -0.5)})
    else:
        E, dx, sx = m["E"], m["dx"], m["S"] * m["dx"]
        out.update({
            "router": ((d, E), F32, d ** -0.5),
            "e_gate": ((E, d, dx), BF16, d ** -0.5),
            "e_up": ((E, d, dx), BF16, d ** -0.5),
            "e_down": ((E, dx, d), BF16, dx ** -0.5),
        })
        if sx:
            out.update({"s_gate": ((d, sx), BF16, d ** -0.5),
                        "s_up": ((d, sx), BF16, d ** -0.5),
                        "s_down": ((sx, d), BF16, sx ** -0.5)})
    return out


def global_leaves(conf: dict) -> dict:
    m = dims(conf)
    return {"embed": ((m["V"], m["d"]), BF16, 1.0),
            "final_norm": ((m["d"],), F32, None),
            "lm_head": ((m["d"], m["V"]), BF16, m["d"] ** -0.5)}


def seed_key(seed: int):
    """A key from any non-negative integer seed (wider than 32 bits too)."""
    key = jax.random.key(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, word & 0xFFFFFFFF)
    return key


def draw(key, name: str, layer, shape, dtype, std):
    """One leaf: a pure function of (key, name, layer, shape, dtype)."""
    if std is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    return jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype)


def layer_weights(key, conf: dict, layer: int) -> dict:
    """Every leaf of one layer (the reference's unit of work)."""
    kind = layer_kinds(conf)[layer]
    return {n: draw(key, n, layer, *spec)
            for n, spec in layer_leaves(conf, kind).items()}


def global_weights(key, conf: dict) -> dict:
    return {n: draw(key, n, 0, *spec)
            for n, spec in global_leaves(conf).items()}


def stacked_layers(key, conf: dict, layers: list[int]) -> dict:
    """The leaves of ``layers`` (all of one kind), stacked on axis 0."""
    kind = layer_kinds(conf)[layers[0]]
    idx = jnp.asarray(layers, jnp.uint32)
    return {n: jax.vmap(lambda l, n=n, s=spec: draw(key, n, l, *s))(idx)
            for n, spec in layer_leaves(conf, kind).items()}
