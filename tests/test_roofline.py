"""HLO cost-analysis correctness (the §Roofline substrate)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze, parse_computations


def test_xla_cost_analysis_misses_trip_counts():
    """Documents WHY hlo_analysis exists: XLA counts while bodies once."""
    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(scanned).lower(x, w).compile()
    xla = c.cost_analysis()
    if isinstance(xla, list):
        xla = xla[0]
    assert xla["flops"] == pytest.approx(2 * 128 ** 3)  # 1x, not 10x


def test_analyzer_multiplies_trip_counts():
    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    t = jax.jit(scanned).lower(x, w).compile().as_text()
    c = analyze(t)
    assert c.flops == pytest.approx(10 * 2 * 128 ** 3)


def test_analyzer_nested_scans():
    def nested(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    t = jax.jit(nested).lower(x, w).compile().as_text()
    c = analyze(t)
    assert c.flops == pytest.approx(12 * 2 * 64 ** 3)


def test_analyzer_flops_exact_single_dot():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((32, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    t = jax.jit(f).lower(a, b).compile().as_text()
    assert analyze(t).flops == pytest.approx(2 * 32 * 512 * 64)


def test_analyzer_counts_collectives_with_trips():
    from tests.helpers import run_multidevice
    run_multidevice("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze
mesh = jax.make_mesh((4,), ('model',),
                     axis_types=(AxisType.Auto,))
def f(x, w):
    def body(c, _):
        h = c @ w                      # w sharded on contraction: psum
        h = jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P(None, None)))
        return h, None
    y, _ = jax.lax.scan(body, x, None, length=5)
    return y
x = jax.ShapeDtypeStruct((32, 256), jnp.float32)
w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
sh_x = NamedSharding(mesh, P(None, 'model'))
sh_w = NamedSharding(mesh, P('model', None))
with mesh:
    t = jax.jit(f, in_shardings=(sh_x, sh_w)).lower(x, w).compile().as_text()
c = analyze(t)
print('coll bytes', c.coll_bytes)
# 5 iterations x all-reduce of (32, 256) f32 result bytes
assert c.coll_bytes >= 5 * 32 * 256 * 4, c.coll_bytes
print('collective trip counting OK')
""", devices=4)


def test_roofline_terms_and_dominance():
    from repro.launch.roofline import Roofline
    r = Roofline(arch="x", shape="y", mesh="m", flops=197e12,
                 hbm_bytes=819e9 / 2, coll_bytes=0.0, coll_breakdown={},
                 peak_memory_bytes=0, model_flops=98.5e12).finalize()
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(0.5)
    assert r.dominant == "compute"
    assert r.roofline_fraction == pytest.approx(0.5)
