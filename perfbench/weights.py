"""Seeded weights of a configuration, shared by the harness and the reference.

Every weight is a pure function of ``(seed, leaf name, layer)``: the
harness draws a whole model in one jitted call (layers stacked by a vmap
over layer keys), and the reference draws one layer at a time and gets
the same numbers.  Which leaves a layer has, with their shapes, types
and scales, is the architecture's own: its reference module
(``reference/<model_type>.py``) gives the tables that these functions
draw.  Nothing here imports the program under test.

Each matrix is drawn directly in the type it is served in, normal with
the standard deviation its table gives (about 1/sqrt(fan-in), so that
every layer's output has about unit scale); a std of None means ones,
as for norm scales.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


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


def layer_weights(key, table: dict, layer: int) -> dict:
    """Every leaf of one layer (the reference's unit of work);
    ``table``: name -> (shape, dtype, std) of that layer's kind."""
    return {n: draw(key, n, layer, *spec) for n, spec in table.items()}


def global_weights(key, table: dict) -> dict:
    return {n: draw(key, n, 0, *spec) for n, spec in table.items()}


def stacked_layers(key, table: dict, layers: list[int]) -> dict:
    """The leaves of ``layers`` (all of the kind ``table`` describes),
    stacked on axis 0."""
    idx = jnp.asarray(layers, jnp.uint32)
    return {n: jax.vmap(lambda l, n=n, s=spec: draw(key, n, l, *s))(idx)
            for n, spec in table.items()}


def model(seed: int, global_table: dict, stacks: list) -> tuple:
    """The whole model, drawn on the device in one jitted call: the
    global leaves, and for each ``(table, layers)`` of ``stacks`` those
    layers' leaves stacked."""
    def build(key):
        return (global_weights(key, global_table),
                [stacked_layers(key, t, layers) for t, layers in stacks])
    return jax.jit(build)(seed_key(seed))
