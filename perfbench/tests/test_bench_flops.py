"""Operation and byte counts against hand counts at small shapes.

A model's count is its architecture's (``token_ops`` of the reference
module its ``model_type`` names); a DeepSeekMoE configuration without
experts is Llama's model, and counts alike."""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest  # noqa: E402

import flops  # noqa: E402


def test_flash_attention_counts():
    # S=4 causal: 10 (query, key) pairs; each costs 2*D for QK and 2*D
    # for PV per head: 10 * 4 * 8 * H=2 = 640
    ops, byts = flops.flash_attention(S=4, H=2, KV=1, D=8)
    assert ops == 640
    # q and o: 2 * (4*2*8); k and v: 2 * (4*1*8); 2 bytes each
    assert byts == (2 * 64 + 2 * 32) * 2
    full, _ = flops.flash_attention(S=4, H=2, KV=1, D=8, causal=False)
    assert full == 16 * 4 * 8 * 2


def test_paged_decode_attention_counts():
    ops, byts = flops.paged_decode_attention([3, 5], H=4, KV=2, D=8)
    assert ops == 4 * 4 * 8 * 8          # 8 keys in all
    assert byts == (2 * 2 * 8 * 8 + 2 * 4 * 8 * 2) * 2


CONF = {"model_type": "llama", "hidden_size": 8, "intermediate_size": 16,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
        "num_hidden_layers": 2, "vocab_size": 32}
ARCHS = [pytest.param(CONF, id="llama"),
         pytest.param(dict(CONF, model_type="deepseek"), id="deepseek")]


@pytest.mark.parametrize("conf", ARCHS)
def test_dense_token_ops(conf):
    # per layer: q,k,v: 2*8*(2+2)*4 = 256; o: 2*2*4*8 = 128;
    # attention over 3 keys: 4*2*4*3 = 96; SwiGLU: 6*8*16 = 768
    per_layer = 256 + 128 + 96 + 768
    assert flops.token_ops(conf, 3, False) == 2 * per_layer
    assert flops.token_ops(conf, 3, True) == 2 * per_layer + 2 * 8 * 32


def test_moe_counts_routed_and_shared_experts_only():
    conf = dict(CONF, model_type="deepseek", n_routed_experts=64,
                num_experts_per_tok=6, moe_intermediate_size=4,
                n_shared_experts=2, first_k_dense_replace=1,
                norm_topk_prob=False)
    attn = 256 + 128 + 4 * 2 * 4 * 1
    dense = attn + 768
    moe = attn + 6 * 8 * 4 * (6 + 2) + 2 * 8 * 64
    assert flops.token_ops(conf, 1, False) == dense + moe


@pytest.mark.parametrize("conf", ARCHS)
def test_prompt_ops_sum_positions(conf):
    assert flops.prompt_ops(conf, 2, 3) == sum(
        flops.token_ops(conf, p + 1, False) for p in (2, 3, 4))


def test_a_llama_configuration_has_no_experts():
    conf = dict(CONF, n_routed_experts=64)
    with pytest.raises(ValueError, match="deepseek"):
        flops.token_ops(conf, 1, False)


def test_unknown_device_is_an_error():
    assert flops.peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v99")
