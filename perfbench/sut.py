"""The system under test: the program's config, weights, engine and fleet.

This module and the architectures' program adapters
(``programs/<model_type>.py``, found by ``cells.program``) are the only
parts of the benchmark that import the program (``repro``).  It builds
the program's ``ModelConfig`` from a configuration file through the
adapter, hands the program weights drawn by ``weights.py`` from the
reference's leaf table (one jitted call, on the device, in the served
types), and stands up the normal serving path: one ``PagedEngine`` with
the prefix cache armed behind a ``FleetController``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax

import cells
import weights as W

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.base import ModelConfig  # noqa: E402
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
    return cells.program(conf).model_config(name, conf)


def _stacks(cfg: ModelConfig) -> list[list[list[int]]]:
    """For each block of the program's tree, the layers behind each of
    its positions: a block repeats its period of layers, and each
    position's leaves are stacked over the repeats."""
    out, i = [], 0
    for b in cfg.blocks:
        p = len(b.layers)
        out.append([[i + r * p + j for r in range(b.repeats)]
                    for j in range(p)])
        i += p * b.repeats
    return out


def program_params(conf: dict, cfg: ModelConfig, seed: int):
    """The program's parameter tree, drawn on the device in one call."""
    ref, prog = cells.reference(conf), cells.program(conf)
    kinds = ref.layer_kinds(conf)
    stacks = _stacks(cfg)
    flat = [s for block in stacks for s in block]
    if sorted(i for s in flat for i in s) != list(range(len(kinds))) \
            or any(len({kinds[i] for i in s}) != 1 for s in flat):
        raise RuntimeError("the program's blocks do not follow the "
                           "reference's layers")
    g, drawn = W.model(seed, ref.global_leaves(conf),
                       [(ref.layer_leaves(conf, kinds[s[0]]), s)
                        for s in flat])
    drawn = iter(drawn)
    params = {**prog.global_tree(g, cfg),
              "blocks": [[prog.layer_tree(next(drawn), kinds[s[0]])
                          for s in block] for block in stacks]}
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
