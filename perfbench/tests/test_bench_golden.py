"""What the benchmark draws and counts for its configurations, pinned.

The values were recorded before each architecture's leaf table, draw and
operation counts moved into files of their own (``reference/llama.py``,
``reference/deepseek.py``, ``programs/``); the weights a cell serves and
the operations ``device.mfu`` counts must stay exactly these:

* ``deepseek-7b-pp2``'s leaf table: every leaf's name, shape, dtype and
  std, the crc32 of its name that keys its draw, and its layer;
* its ``flops.token_ops`` at three contexts, and a prompt's ops;
* at a tiny width, one Llama layer and one DeepSeekMoE layer drawn
  alone, the global leaves, layers drawn stacked, and the program's
  whole parameter tree drawn in its one jitted call, all bitwise.
"""

from __future__ import annotations

import hashlib
import os
import sys
import zlib
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import cells  # noqa: E402
import flops  # noqa: E402
import sut  # noqa: E402
import weights as W  # noqa: E402

DENSE = {"model_type": "llama", "hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "num_hidden_layers": 2, "vocab_size": 1024}
MOE = dict(DENSE, model_type="deepseek", num_key_value_heads=4,
           num_hidden_layers=3, n_routed_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=64, n_shared_experts=2,
           first_k_dense_replace=1, norm_topk_prob=True)

TABLE_SHA = "65776df1efa128d04f3322b3a686e3939536dec788f111265fe68a40fc1fd42f"
TOKEN_OPS = {(1, False): 6071500800.0, (1, True): 6910361600.0,
             (2048, False): 6574571520.0, (2048, True): 7413432320.0,
             (4096, False): 7077888000.0, (4096, True): 7916748800.0}
PROMPT_OPS = 263105740800.0          # tokens 2040..2079 of a prompt
DRAWS = {
    "llama-layer0":
        "92b41080c32aa80042e61a6a01cbd2396c99229ab53f6ed18e32f4b4da34d84a",
    "llama-global":
        "837c7d789e6b4dc1a642057c492497aae20af41a3a6dde1fdc07895a03668f74",
    "deepseek-layer0":
        "2da11a4c21d051ded97261519d874cae09808a959869c044fd5ebf4b43f31d5f",
    "deepseek-layer2":
        "b1ad2763dea76a71e5bce7fda5616a32054c544250ee1f1f950fb845a284c3b2",
    "llama-stacked":
        "a2d09314a23821b0570b339e5e3e4d47c545b566d142eaa2cc3f08dea95b1e2a",
    "deepseek-stacked":
        "dbdd3064d9cfbbf003b74059775f4be3cb3e8cdb62cdcaf38aebd39a124c5c8f",
    "llama-program":
        "fc81216a1e8a5184c0b6b737f5dad0a254062c740047d09326a7478f5b705f90",
    "deepseek-program":
        "1d06d38a5815dd08792c8a112d13df196874447b6a7912cf5f8ccd0c03f8c382",
}


@pytest.fixture(scope="module")
def ds7b():
    bench = cells.load_benchmark()
    return cells.config(bench, "deepseek-7b-pp2")


def leaf_sha(tree) -> str:
    """sha256 over every leaf's path, dtype and bytes, in path order."""
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, a in sorted(flat, key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_deepseek_7b_leaf_table_is_the_parents(ds7b):
    ref = cells.reference(ds7b)
    rows = [("global", 0, n, tuple(shape), jnp.dtype(dt).name, repr(std),
             zlib.crc32(n.encode()))
            for n, (shape, dt, std) in sorted(ref.global_leaves(ds7b).items())]
    for layer, kind in enumerate(ref.layer_kinds(ds7b)):
        rows += [(kind, layer, n, tuple(shape), jnp.dtype(dt).name,
                  repr(std), zlib.crc32(n.encode()))
                 for n, (shape, dt, std)
                 in sorted(ref.layer_leaves(ds7b, kind).items())]
    assert len(rows) == 3 + 15 * 9
    assert hashlib.sha256(repr(rows).encode()).hexdigest() == TABLE_SHA


def test_deepseek_7b_token_ops_are_the_parents(ds7b):
    for (ctx, head), want in TOKEN_OPS.items():
        assert flops.token_ops(ds7b, ctx, head) == want
    assert flops.prompt_ops(ds7b, 2040, 40) == PROMPT_OPS


def _draw(name):
    key = W.seed_key(2 ** 40 + 3)
    arch, what = name.split("-")
    conf = DENSE if arch == "llama" else MOE
    ref = cells.reference(conf)
    kinds = ref.layer_kinds(conf)
    if what == "global":
        return W.global_weights(key, ref.global_leaves(conf))
    if what == "stacked":
        layers = [0, 1] if arch == "llama" else [1, 2]
        table = ref.layer_leaves(conf, kinds[layers[0]])
        return jax.jit(lambda k: W.stacked_layers(k, table, layers))(key)
    if what == "program":
        return sut.program_params(conf, sut.model_config("t", conf),
                                  2 ** 33 + 7)
    layer = int(what[len("layer"):])
    return W.layer_weights(key, ref.layer_leaves(conf, kinds[layer]), layer)


@pytest.mark.parametrize("name", sorted(DRAWS))
def test_tiny_draws_are_the_parents(name):
    assert leaf_sha(_draw(name)) == DRAWS[name]
