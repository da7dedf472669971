"""The program's side of the Llama architecture (``model_type``
``"llama"``): its ``ModelConfig``, and where each leaf of the reference's
table (``reference/llama.py``) goes in its parameter tree.

Loaded by ``sut.py``, which puts the program (``src``) on the path.
"""

from __future__ import annotations

from repro.configs.base import BlockDef, LayerSpec, ModelConfig


def _blocks(kinds: list[str]) -> tuple:
    """One block of ``LayerSpec("attn", kind)`` per run of like layers."""
    out, i = [], 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        out.append(BlockDef((LayerSpec("attn", kinds[i]),), repeats=j - i))
        i = j
    return tuple(out)


def model_config(name: str, conf: dict, *, kinds=None,
                 moe=None) -> ModelConfig:
    """The program's config; ``kinds`` (the FFN of each layer, all dense
    if not given) and ``moe`` for an architecture built on this one."""
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    if kinds is None:
        kinds = ["dense"] * conf["num_hidden_layers"]
    return ModelConfig(
        name=name, family="moe" if moe else "dense", d_model=d,
        num_heads=H, num_kv_heads=conf.get("num_key_value_heads", H),
        head_dim=conf.get("head_dim", d // H),
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        blocks=_blocks(kinds), moe=moe,
        rope_theta=float(conf.get("rope_theta", 10000.0)),
        norm_eps=float(conf.get("rms_norm_eps", 1e-6)),
        act=conf.get("hidden_act", "silu"),
        max_position=conf.get("max_position_embeddings", 1 << 20),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)))


def global_tree(g: dict, cfg: ModelConfig) -> dict:
    """The program's tree of everything outside its blocks."""
    tree = {"embed": g["embed"], "final_norm": {"scale": g["final_norm"]}}
    if not cfg.tie_embeddings:
        tree["lm_head"] = g["lm_head"]
    return tree


def attention_tree(leaves: dict) -> dict:
    return {"ln": {"scale": leaves["attn_norm"]},
            **{n: leaves[n] for n in ("wq", "wk", "wv", "wo")}}


def layer_tree(leaves: dict, kind: str) -> dict:
    """One layer's leaves (or a stack of like layers') -> the program's
    per-layer tree."""
    return {"attn": attention_tree(leaves),
            "mlp": {"ln": {"scale": leaves["mlp_norm"]},
                    **{n: leaves[n] for n in ("w_gate", "w_up", "w_down")}}}
