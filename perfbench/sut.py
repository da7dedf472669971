"""The system under test: the program's config, weights, engine and fleet.

This is the only module of the benchmark that imports the program
(``repro``).  It builds the program's ``ModelConfig`` from a
configuration file, hands the program weights drawn by ``weights.py``
(one jitted call, on the device, in the served types), and stands up the
normal serving path: one ``PagedEngine`` with the prefix cache armed
behind a ``FleetController``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax

import weights as W

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.base import BlockDef, LayerSpec, ModelConfig, MoESpec  # noqa: E402
from repro.core import daemon  # noqa: E402
from repro.fleet import (EngineHandle, FleetController, Rebalancer,  # noqa: E402
                         RequestSpec)
from repro.kernels import ops  # noqa: E402
from repro.models.init import abstract_params  # noqa: E402
from repro.models.model import forward  # noqa: E402
from repro.serving.paged import PagedEngine  # noqa: E402

__all__ = ["RequestSpec", "ops", "model_config", "program_params", "serve",
           "teacher_forced_argmax"]


def model_config(name: str, conf: dict) -> ModelConfig:
    m = W.dims(conf)
    kinds = W.layer_kinds(conf)
    blocks, i = [], 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        blocks.append(BlockDef((LayerSpec("attn", kinds[i]),), repeats=j - i))
        i = j
    moe = None
    if m["E"]:
        moe = MoESpec(num_experts=m["E"], top_k=m["k"], d_expert=m["dx"],
                      num_shared=m["S"])
    return ModelConfig(
        name=name, family="moe" if moe else "dense", d_model=m["d"],
        num_heads=m["H"], num_kv_heads=m["KV"], head_dim=m["Dh"],
        d_ff=m["ff"], vocab_size=m["V"], blocks=tuple(blocks), moe=moe,
        rope_theta=float(conf.get("rope_theta", 10000.0)),
        norm_eps=float(conf.get("rms_norm_eps", 1e-6)),
        act=conf.get("hidden_act", "silu"),
        max_position=conf.get("max_position_embeddings", 1 << 20),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)))


def _layer_tree(leaves: dict, kind: str) -> dict:
    """Canonical leaf names -> the program's per-layer parameter tree."""
    t = {"attn": {"ln": {"scale": leaves["attn_norm"]},
                  **{n: leaves[n] for n in ("wq", "wk", "wv", "wo")}}}
    if kind == "dense":
        t["mlp"] = {"ln": {"scale": leaves["mlp_norm"]},
                    **{n: leaves[n] for n in ("w_gate", "w_up", "w_down")}}
    else:
        t["moe"] = {"ln": {"scale": leaves["mlp_norm"]},
                    "router": leaves["router"], "w_gate": leaves["e_gate"],
                    "w_up": leaves["e_up"], "w_down": leaves["e_down"]}
        if "s_gate" in leaves:
            t["moe"]["shared"] = {"w_gate": leaves["s_gate"],
                                  "w_up": leaves["s_up"],
                                  "w_down": leaves["s_down"]}
    return t


def program_params(conf: dict, cfg: ModelConfig, seed: int):
    """The program's parameter tree, drawn on the device in one call."""
    kinds = W.layer_kinds(conf)

    def build(key):
        g = W.global_weights(key, conf)
        blocks, i = [], 0
        for b in cfg.blocks:
            layers = list(range(i, i + b.repeats))
            i += b.repeats
            blocks.append([_layer_tree(W.stacked_layers(key, conf, layers),
                                       kinds[layers[0]])])
        tree = {"embed": g["embed"], "blocks": blocks,
                "final_norm": {"scale": g["final_norm"]}}
        if not cfg.tie_embeddings:
            tree["lm_head"] = g["lm_head"]
        return tree

    params = jax.jit(build)(W.seed_key(seed))
    want = abstract_params(cfg)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise RuntimeError("drawn weights do not match the program's "
                           "parameter schema")
    return params


# Shadow checkpoints copy every live slot to the host so that a failed
# engine's requests resume on a survivor.  A one-engine fleet has no
# survivor, so its deployment turns them off (the program takes no 0 here).
NO_SHADOW_SYNC = 1 << 62


def serve(cfg: ModelConfig, params, serving: dict, max_len: int):
    """One PagedEngine, its prefix cache armed, behind the fleet."""
    engine = PagedEngine(cfg, params, page_size=serving["page_size"],
                         pages=serving["kv_pages"], rows=serving["rows"],
                         max_len=max_len, seed=0, prefix_cache=True)
    fleet = FleetController([EngineHandle("e0", engine, daemon.CLOUD)],
                            balancer=Rebalancer(sync_every=NO_SHADOW_SYNC),
                            queue_limit=1 << 20)
    return fleet, engine


def teacher_forced_argmax(cfg: ModelConfig, params, seqs, rows,
                          length: int) -> list:
    """The token the program's own full forward pass (no cache, the
    prefill kernels) puts first at each of ``rows[i]`` of ``seqs[i]``;
    sequences are padded at the end to ``length``."""
    import numpy as np

    @jax.jit
    def first(p, toks, r):
        logits = forward(p, {"tokens": toks}, cfg=cfg, mode="train",
                         remat=False)[0]
        return jax.numpy.argmax(logits[0, r, :cfg.vocab_size], -1)

    out = []
    for seq, r in zip(seqs, rows):
        toks = np.zeros((1, length), np.int32)
        toks[0, :len(seq)] = seq
        rr = np.zeros((length,), np.int32)
        rr[:len(r)] = r
        out.append(np.asarray(first(params, toks, rr))[:len(r)])
    return out
