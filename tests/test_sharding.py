"""Sharding rules, MoE expert-parallel equivalence, pjit train on a
multi-device mesh (subprocess)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from tests.helpers import run_multidevice


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
    empty = False


def test_resolve_basic():
    m = FakeMesh({"data": 16, "model": 16})
    assert shd.resolve(("embed", "mlp"), m, (1024, 4096)) == P(None, "model")
    assert shd.resolve(("batch", None), m, (256, 128)) == P("data")


def test_resolve_auto_degrade_non_divisible():
    m = FakeMesh({"data": 16, "model": 16})
    # 8 heads can't shard over 16 -> replicate
    assert shd.resolve(("embed", "heads", "head_dim"), m,
                       (2560, 8, 256)) == P()
    # 32 heads shard fine
    assert shd.resolve(("embed", "heads", "head_dim"), m,
                       (2560, 32, 128)) == P(None, "model")


def test_resolve_no_axis_reuse():
    m = FakeMesh({"data": 16, "model": 16})
    # kv_heads takes model; kv_dim must not reuse it
    spec = shd.resolve(("batch", "cache_seq", "kv_heads", "kv_dim"), m,
                       (128, 1024, 16, 128))
    assert spec == P("data", None, "model")
    # kv_heads=8 fails -> kv_dim picks model up
    spec = shd.resolve(("batch", "cache_seq", "kv_heads", "kv_dim"), m,
                       (128, 1024, 8, 128))
    assert spec == P("data", None, None, "model")


def test_resolve_multi_axis_batch():
    m = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert shd.resolve(("batch", None), m, (256, 4096)) == \
        P(("pod", "data"))
    # batch=1 (long_500k): replicate via override
    assert shd.resolve(("batch", None), m, (1, 4096),
                       overrides={"batch": None}) == P()


def test_param_specs_match_schema_structure():
    from repro.configs import get
    from repro.models import schema
    from repro.models.init import abstract_params
    cfg = get("stablelm-12b")
    tree = schema.model_schema(cfg)
    params = abstract_params(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda x: 0, tree,
                     is_leaf=lambda x: isinstance(x, schema.ParamDef))) \
        == jax.tree.structure(jax.tree.map(lambda x: 0, params))


def test_moe_ep_equals_dense_oracle():
    """Expert-parallel (a2a + replicated modes) == dense oracle on a
    real multi-device mesh."""
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get
from repro.configs.tiny import make_tiny
from repro.models.init import init_params
from repro.models import moe as moe_mod

cfg = make_tiny(get('granite-moe-1b-a400m'))
cfg = cfg.replace(dtype='float32',
                  moe=cfg.moe.__class__(num_experts=8, top_k=2,
                                        d_expert=32, num_shared=1,
                                        capacity_factor=8.0))
params = init_params(cfg, jax.random.key(0))
p = None
for g in params['blocks']:
    for lp in g:
        if 'moe' in lp:
            p = jax.tree.map(lambda a: a[0], lp['moe'])
if p is None: raise SystemExit('no moe layer')

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)

# a2a mode: tokens divide the full mesh
x = jnp.asarray(rng.standard_normal((8, 4, cfg.d_model)), jnp.float32)
dense, aux_d = moe_mod.moe_dense(p, x, cfg)
with mesh:
    ep, aux_e = moe_mod.moe_ep(p, x, cfg, mesh)
err = float(jnp.abs(dense - ep).max() / (jnp.abs(dense).max() + 1e-9))
print('a2a mode rel err:', err)
assert err < 1e-3, err

# replicated mode: tiny token count (decode-like)
x = jnp.asarray(rng.standard_normal((2, 1, cfg.d_model)), jnp.float32)
dense, _ = moe_mod.moe_dense(p, x, cfg)
with mesh:
    ep, _ = moe_mod.moe_ep(p, x, cfg, mesh)
err = float(jnp.abs(dense - ep).max() / (jnp.abs(dense).max() + 1e-9))
print('replicated mode rel err:', err)
assert err < 1e-3, err
print('MoE EP == dense OK')
""", devices=8)


def test_pjit_train_step_on_mesh_matches_single_device():
    """One train step under a 2x2 mesh == the same step on 1 device."""
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get, SHAPES
from repro.configs.tiny import make_tiny
from repro.models.init import init_params
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.training.train import TrainConfig, train_step
from repro.launch import steps as lsteps

cfg = make_tiny(get('llama-1.5b')).replace(dtype='float32')
tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3), z_loss=0.0)
params = init_params(cfg, jax.random.key(0))
opt = init_opt_state(params)
rng = np.random.default_rng(0)
batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                               jnp.int32)}

p1, o1, m1 = train_step(params, opt, batch, cfg=cfg, tcfg=tcfg)

mesh = jax.make_mesh((2, 2), ('data', 'model'),
                     axis_types=(AxisType.Auto,) * 2)
rules = {}
with mesh:
    p2, o2, m2 = jax.jit(
        lambda p, o, b: train_step(p, o, b, cfg=cfg, tcfg=tcfg,
                                   mesh=mesh, rules=rules))(params, opt,
                                                            batch)
print('loss single %.6f mesh %.6f' % (m1['loss'], m2['loss']))
assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-3
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    d = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    assert d < 1e-3, d
print('pjit train parity OK')
""", devices=4)


def test_pallas_attention_on_mesh_matches_single_device():
    """Pallas kernels cannot be partitioned automatically, so on a mesh
    they run per device inside a shard_map: the prefill forward (kernels
    in interpret mode) and the weights placed by an Engine on the mesh
    must match the one-device results."""
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get
from repro.configs.tiny import make_tiny
from repro.kernels import ops
from repro.launch.mesh import make_debug_mesh
from repro.models.init import init_params
from repro.models.model import forward
from repro.serving.engine import Engine

cfg = make_tiny(get('llama-1.5b')).replace(dtype='float32')
params = init_params(cfg, jax.random.key(0))
toks = jnp.asarray(np.random.default_rng(0).integers(0, 500, (2, 40)),
                   jnp.int32)
ops.set_backend('interpret')
run = lambda mesh: jax.jit(lambda p, t: forward(
    p, {'tokens': t}, cfg=cfg, mode='train', mesh=mesh)[0])
ref = run(None)(params, toks)
# (1, 4): query heads split, kv heads (2) do not divide -> heads whole;
# (2, 2): both head counts split alike; (4, 1): batch split only
for data, model in [(1, 4), (2, 2), (4, 1)]:
    mesh = make_debug_mesh(model=model, data=data)
    eng = Engine(cfg, params, slots=2, max_len=64, mesh=mesh)
    w = eng.params['blocks'][0][0]['attn']['wq']
    assert w.sharding.mesh.shape == mesh.shape, w.sharding
    err = float(jnp.abs(run(mesh)(eng.params, toks) - ref).max())
    print((data, model), err)
    assert err < 1e-4, ((data, model), err)
print('sharded pallas attention OK')
""", devices=4)
