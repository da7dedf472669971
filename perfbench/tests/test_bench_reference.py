"""The program's serving paths against the float32 reference, on logits.

At a tiny size on the CPU, with the Pallas kernels in interpret mode:
a cold paged prefill (the program's prefill forward writing the page
pools), then one-token decode steps that read the cached pages through
the paged decode-attention kernel -- the path both a suffix prefill and
paged decode take -- against the reference's full forward pass over the
same tokens.  Each row's logits must agree to bf16 rounding, and the
token the program puts first must lie within the served-gap limit of the
reference's best.  Each architecture's reference and program adapter are
found by the configuration's ``model_type`` (``cells``).
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cells  # noqa: E402
import sut  # noqa: E402
import weights as W  # noqa: E402
from reference import check  # noqa: E402

DENSE = {"model_type": "llama", "hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "num_hidden_layers": 2, "vocab_size": 1024}
# norm_topk_prob true: the only routing the program implements today
MOE = dict(DENSE, model_type="deepseek", num_key_value_heads=4,
           num_hidden_layers=3, n_routed_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=64, n_shared_experts=2,
           first_k_dense_replace=1, norm_topk_prob=True)
# DeepSeekMoE as published
MOE_PUBLISHED = dict(MOE, norm_topk_prob=False)
ARCHS = [pytest.param(DENSE, id="llama"), pytest.param(MOE, id="deepseek")]
PROMPT, TOTAL, PAGE = 40, 56, 16
# bf16 weights and activations against float32: measured 0.03-0.05 on
# logits of size ~4 at this width; a wrong page, position or mask moves
# logits by their own scale
LOGIT_TOL = 0.1
GAP_TOL = 0.1


def program_logits(conf, seed, seq):
    from repro.models.layers import make_paged_attn_cache
    from repro.models.model import forward
    from repro.serving.paged import _weave
    cfg = sut.model_config("t", conf)
    params = sut.program_params(conf, cfg, seed)
    n_pages = -(-len(seq) // PAGE)
    table = jnp.arange(n_pages, dtype=jnp.int32)[None]
    one = make_paged_attn_cache(cfg, n_pages, PAGE)
    caches = [[{"attn": jax.tree.map(
        lambda a, r=b.repeats: jnp.broadcast_to(a, (r,) + a.shape), one)}
        for _ in b.layers] for b in cfg.blocks]
    lg, caches, _ = forward(params, {"tokens": jnp.asarray(seq[:PROMPT])[None]},
                            cfg=cfg, mode="prefill",
                            caches=_weave(caches, table))
    rows = [lg[0]]
    for i in range(PROMPT, len(seq)):
        lg, caches, _ = forward(
            params, {"tokens": jnp.asarray(seq[i:i + 1])[None]}, cfg=cfg,
            mode="decode", positions=jnp.full((1, 1), i, jnp.int32),
            caches=_weave(caches, table))
        rows.append(lg[0])
    out = jnp.concatenate(rows).astype(jnp.float32)
    return np.asarray(out)[:, :conf["vocab_size"]]


@pytest.mark.parametrize("conf", [
    pytest.param(DENSE, id="dense"), pytest.param(MOE, id="moe"),
    pytest.param(MOE_PUBLISHED, id="moe-norm_topk_prob-false",
                 marks=pytest.mark.xfail(
                     strict=True, raises=NotImplementedError,
                     reason="repro.models.moe._router always renormalises"))])
def test_paged_prefill_and_decode_match_reference(conf):
    seed = 3
    seq = np.random.default_rng(seed).integers(
        0, conf["vocab_size"], TOTAL).astype(np.int32)
    sut.ops.set_backend("interpret")
    try:
        prog = program_logits(conf, seed, seq)
    finally:
        sut.ops.set_backend(None)
    exact = cells.reference(conf).logits_at(conf, seed, [seq],
                                            [list(range(TOTAL))])[0]
    diff = np.abs(prog - exact)
    assert np.median(diff) < LOGIT_TOL / 10
    if "n_routed_experts" not in conf:
        # (an expert layer may route a near-tied token elsewhere in bf16)
        assert diff.max() < LOGIT_TOL
    assert check.chosen_gap([exact], [prog.argmax(-1)]) < GAP_TOL


@pytest.mark.parametrize("conf", ARCHS)
def test_stacked_weights_equal_per_layer_weights(conf):
    ref = cells.reference(conf)
    key = W.seed_key(2 ** 40 + 3)
    layers = [1, 2] if "n_routed_experts" in conf else [0, 1]
    table = ref.layer_leaves(conf, ref.layer_kinds(conf)[layers[0]])
    stacked = jax.jit(lambda k: W.stacked_layers(k, table, layers))(key)
    for i, layer in enumerate(layers):
        one = W.layer_weights(key, table, layer)
        for name, leaf in one.items():
            assert leaf.dtype == stacked[name].dtype
            got, want = np.asarray(stacked[name][i]), np.asarray(leaf)
            if leaf.dtype == jnp.bfloat16:
                np.testing.assert_array_equal(got, want)
            else:  # compiled and eager float32 may part in the last bit
                np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("conf", ARCHS)
def test_program_tree_takes_the_drawn_weights(conf):
    """The last layer's leaves, drawn alone and mapped by the program
    adapter, are the last slice of the program's last block."""
    ref, prog = cells.reference(conf), cells.program(conf)
    cfg = sut.model_config("t", conf)
    params = sut.program_params(conf, cfg, 5)
    layer = conf["num_hidden_layers"] - 1
    kind = ref.layer_kinds(conf)[layer]
    one = prog.layer_tree(W.layer_weights(
        W.seed_key(5), ref.layer_leaves(conf, kind), layer), kind)
    got = jax.tree.map(lambda a: a[-1], params["blocks"][-1][-1])
    assert jax.tree.structure(got) == jax.tree.structure(one)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
        assert a.dtype == b.dtype
        if a.dtype == jnp.bfloat16:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:  # compiled and eager float32 may part in the last bit
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
    if kind == "moe":
        moe = params["blocks"][-1][-1]["moe"]
        assert moe["router"].dtype == jnp.float32
        assert moe["w_gate"].dtype == jnp.bfloat16


@pytest.mark.parametrize("norm", [False, True],
                         ids=["norm_topk_prob-false", "norm_topk_prob-true"])
def test_gates_follow_norm_topk_prob(norm):
    """Without ``norm_topk_prob`` each chosen expert's gate is its router
    probability; with it the chosen gates are renormalised to sum to 1."""
    conf = dict(MOE, norm_topk_prob=norm)
    ds = cells.reference(conf)
    h = jax.random.normal(jax.random.key(1), (16, 128))
    router = jax.random.normal(jax.random.key(2), (128, 8)) * 128 ** -0.5
    probs = np.asarray(jax.nn.softmax(jnp.einsum(
        "td,de->te", h, router, precision=jax.lax.Precision.HIGHEST), -1))
    g = np.asarray(ds.gates(h, router, conf, None))
    chosen = g > 0
    top2 = np.argsort(probs, -1)[:, -2:]
    assert (chosen.sum(-1) == 2).all()
    assert chosen[np.arange(16)[:, None], top2].all()
    if norm:
        np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-6)
    else:
        np.testing.assert_allclose(g[chosen], probs[chosen], rtol=1e-6)
        assert (g.sum(-1) < 1 - 1e-3).all()


def test_experts_need_norm_topk_prob_stated():
    conf = {k: v for k, v in MOE.items() if k != "norm_topk_prob"}
    with pytest.raises(KeyError, match="norm_topk_prob"):
        cells.reference(conf).logits_at(conf, 1, [np.zeros(4, np.int32)],
                                        [[0]])
    with pytest.raises(KeyError, match="norm_topk_prob"):
        sut.model_config("t", conf)
