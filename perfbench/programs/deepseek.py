"""The program's side of DeepSeekMoE (``model_type`` ``"deepseek"``):
Llama's (``programs/llama.py``), with the program's expert layer
(``MoESpec``, the ``moe`` subtree) in the layers the reference
(``reference/deepseek.py``) makes expert layers.

The program's router renormalises its top-k weights and has no switch
for it (``repro.models.moe._router``), so a configuration that states
``norm_topk_prob: false`` is refused rather than served otherwise than
it says.
"""

from __future__ import annotations

from repro.configs.base import ModelConfig, MoESpec

from programs import llama as L
from reference import deepseek as R

global_tree = L.global_tree


def model_config(name: str, conf: dict) -> ModelConfig:
    kinds = R.layer_kinds(conf)
    if "moe" not in kinds:
        return L.model_config(name, conf)
    m = R.dims(conf)
    if not conf["norm_topk_prob"]:
        raise NotImplementedError(
            "the program's router always renormalises the top-k weights "
            "(repro.models.moe._router); this configuration states "
            "norm_topk_prob: false")
    spec = MoESpec(num_experts=m["E"], top_k=m["k"], d_expert=m["dx"],
                   num_shared=m["S"])
    return L.model_config(name, conf, kinds=kinds, moe=spec)


def layer_tree(leaves: dict, kind: str) -> dict:
    """One layer's leaves (or a stack of like layers') -> the program's
    per-layer tree."""
    if kind == "dense":
        return L.layer_tree(leaves, kind)
    moe = {"ln": {"scale": leaves["mlp_norm"]},
           "router": leaves["router"], "w_gate": leaves["e_gate"],
           "w_up": leaves["e_up"], "w_down": leaves["e_down"]}
    if "s_gate" in leaves:
        moe["shared"] = {"w_gate": leaves["s_gate"], "w_up": leaves["s_up"],
                         "w_down": leaves["s_down"]}
    return {"attn": L.attention_tree(leaves), "moe": moe}
