"""Compile rehearsals: the main-path Pallas kernels at llama-1.5b widths
(H=16, KV=8, D=128, V=32000), and the paged decode kernel at the
benchmark cell's DeepSeek-7B shapes, compiled for a described TPU v5e
chip.

Nothing runs: the TPU compiler installed next to JAX compiles for a
chip that is described, not attached, and refuses what the chip would
refuse (SMEM vector loads, unaligned blocks, VMEM overflow).  Interpret
mode on the CPU cannot see those faults.  The topology is described
only inside the module fixture below, never at import time, so every
pytest-xdist worker collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.spec_verify import spec_verify

H, KV, D = 16, 8, 128          # llama-1.5b attention widths
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable cannot be read back from the persistent
    # compilation cache without the chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_paged_decode_attention_compiles(one_chip):
    B, pages, ps, NP = 4, 256, 16, 64          # 4 rows x 1024 tokens
    _compile(one_chip,
             lambda q, k, v, pt, pos: paged_decode_attention(
                 q, k, v, pt, pos),
             ((B, 1, H, D), BF16), ((pages, ps, KV, D), BF16),
             ((pages, ps, KV, D), BF16), ((B, NP), jnp.int32),
             ((B,), jnp.int32))


@pytest.mark.parametrize("B", [16, 1], ids=["decode", "suffix"])
def test_paged_decode_attention_compiles_at_ds7b_cell(one_chip, B):
    """DeepSeek-7B's cell: 600 pages of 16 tokens, 144-page tables, 32
    query and 32 KV heads of 128 (MHA), bf16; 16 rows in the decode
    step, one in each suffix token."""
    pages, ps, NP, heads = 600, 16, 144, 32
    _compile(one_chip,
             lambda q, k, v, pt, pos: paged_decode_attention(
                 q, k, v, pt, pos),
             ((B, 1, heads, D), BF16), ((pages, ps, heads, D), BF16),
             ((pages, ps, heads, D), BF16), ((B, NP), jnp.int32),
             ((B,), jnp.int32))


@pytest.mark.parametrize("S", [40, 512, 600])
def test_flash_attention_compiles(one_chip, S):
    _compile(one_chip,
             lambda q, k, v: flash_attention(q, k, v, causal=True),
             ((1, S, H, D), BF16), ((1, S, KV, D), BF16),
             ((1, S, KV, D), BF16))


@pytest.mark.parametrize("V", [32000, 32768])   # vocab, padded vocab
def test_spec_verify_compiles(one_chip, V):
    g = 4
    _compile(one_chip,
             lambda t, dp, tp, kd: spec_verify(
                 t, dp, tp, jax.random.wrap_key_data(kd)),
             ((g,), jnp.int32), ((g, V), jnp.float32),
             ((g + 1, V), jnp.float32), ((2,), jnp.uint32))
