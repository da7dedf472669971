"""Canonical step functions + sharding trees for launch/dry-run.

One builder per shape kind; each returns (jitted_fn, abstract_args) so
the dry-run can ``.lower(*args).compile()`` without allocating anything.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ModelConfig, ShapeSpec
from repro.configs.inputs import batch_specs, cache_specs_abstract
from repro.models.init import abstract_params
from repro.models.model import cache_specs, forward
from repro.optim.adamw import AdamWConfig
from repro.training.train import TrainConfig, train_step


def merged_rules(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    return {**cfg.overrides, **shape.overrides}


def batch_shardings(batch, cfg, mesh, rules):
    logical = {
        "tokens": ("batch", None),
        "positions": ("batch", None),
        "frames": ("batch", None, None),
        "patch_embeds": ("batch", None, None),
        "enc_out": ("batch", None, None),
    }
    return {
        k: NamedSharding(mesh, shd.resolve(logical[k], mesh, v.shape, rules))
        for k, v in batch.items()
    }


def cache_shardings(caches, mesh, rules):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_specs(caches, mesh, rules))


def opt_shardings(pshard, mesh):
    return {"mu": pshard, "nu": pshard,
            "step": NamedSharding(mesh, P())}


def abstract_opt(params_abs):
    return {"mu": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params_abs),
        "nu": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params_abs),
        "step": jax.ShapeDtypeStruct((), jnp.int32)}


def build_train(cfg: ModelConfig, shape: ShapeSpec, mesh,
                microbatches: int = 4):
    rules = merged_rules(cfg, shape)
    tcfg = TrainConfig(optimizer=AdamWConfig(),
                       microbatches=microbatches, remat=True)

    def step(params, opt_state, batch):
        p, o, m = train_step(params, opt_state, batch, cfg=cfg, tcfg=tcfg,
                             mesh=mesh, rules=rules)
        return p, o, m["loss"]

    params_abs = abstract_params(cfg)
    opt_abs = abstract_opt(params_abs)
    batch = batch_specs(cfg, shape)
    pshard = shd.param_shardings(cfg, mesh, rules)
    in_shardings = (pshard, opt_shardings(pshard, mesh),
                    batch_shardings(batch, cfg, mesh, rules))
    out_shardings = (pshard, opt_shardings(pshard, mesh),
                     NamedSharding(mesh, P()))
    fn = jax.jit(step, in_shardings=in_shardings,
                 out_shardings=out_shardings, donate_argnums=(0, 1))
    return fn, (params_abs, opt_abs, batch)


def build_prefill(cfg: ModelConfig, shape: ShapeSpec, mesh):
    rules = merged_rules(cfg, shape)

    def step(params, batch, caches):
        logits, caches, _ = forward(params, batch, cfg=cfg, mode="prefill",
                                    caches=caches, mesh=mesh, rules=rules)
        return logits[:, -1], caches

    params_abs = abstract_params(cfg)
    batch = batch_specs(cfg, shape)
    caches = cache_specs_abstract(cfg, shape)
    pshard = shd.param_shardings(cfg, mesh, rules)
    cshard = cache_shardings(caches, mesh, rules)
    in_shardings = (pshard,
                    batch_shardings(batch, cfg, mesh, rules), cshard)
    out_shardings = (NamedSharding(
        mesh, shd.resolve(("batch", "vocab"), mesh,
                          (shape.global_batch, cfg.padded_vocab), rules)),
        cshard)
    fn = jax.jit(step, in_shardings=in_shardings,
                 out_shardings=out_shardings, donate_argnums=(2,))
    return fn, (params_abs, batch, caches)


def build_decode(cfg: ModelConfig, shape: ShapeSpec, mesh):
    rules = merged_rules(cfg, shape)

    def step(params, batch, caches):
        logits, caches, _ = forward(
            params, {"tokens": batch["tokens"]}, cfg=cfg, mode="decode",
            caches=caches, positions=batch["positions"],
            mesh=mesh, rules=rules)
        return logits[:, 0], caches

    params_abs = abstract_params(cfg)
    batch = batch_specs(cfg, shape)
    caches = cache_specs_abstract(cfg, shape)
    pshard = shd.param_shardings(cfg, mesh, rules)
    cshard = cache_shardings(caches, mesh, rules)
    in_shardings = (pshard,
                    batch_shardings(batch, cfg, mesh, rules), cshard)
    out_shardings = (NamedSharding(
        mesh, shd.resolve(("batch", "vocab"), mesh,
                          (shape.global_batch, cfg.padded_vocab), rules)),
        cshard)
    fn = jax.jit(step, in_shardings=in_shardings,
                 out_shardings=out_shardings, donate_argnums=(2,))
    return fn, (params_abs, batch, caches)


def build(cfg: ModelConfig, shape: ShapeSpec, mesh, **kw):
    if shape.kind == "train":
        return build_train(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh)
    return build_decode(cfg, shape, mesh)
