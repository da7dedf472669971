"""Per-kernel shape/dtype sweeps: pallas_call (interpret=True) vs the
pure-jnp oracles in kernels/ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.spec_verify import spec_verify

RNG = np.random.default_rng(7)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,S,H,KV,D", [
    (1, 128, 4, 4, 64),    # MHA
    (2, 256, 8, 2, 64),    # GQA 4:1
    (1, 128, 4, 1, 128),   # MQA
    (1, 200, 8, 2, 64),    # GQA, length padded up to a block multiple
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["causal", "window", "full", "softcap"])
def test_flash_attention_sweep(B, S, H, KV, D, dtype, mode):
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, D)), dtype)
    kw = dict(causal=True)
    if mode == "window":
        kw = dict(causal=True, window=48)
    elif mode == "full":
        kw = dict(causal=False)
    elif mode == "softcap":
        kw = dict(causal=True, softcap=20.0)
    o = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True,
                        **kw)
    oref = ref.reference_attention(q, k, v, **kw)
    err = float(jnp.abs(o.astype(jnp.float32)
                        - oref.astype(jnp.float32)).max())
    assert err < tol(dtype), (mode, err)


@pytest.mark.parametrize("Sq,H,KV", [
    (600, 4, 4),           # one 512 block plus a padded one
    (520, 8, 2),           # GQA 4:1, padded to 1024
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_padded_lengths(Sq, H, KV, causal):
    """Lengths over the default 512 block that are not a multiple of it:
    padded keys must be masked and padded query rows dropped."""
    D = 64
    q = jnp.asarray(RNG.standard_normal((1, Sq, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, Sq, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, Sq, KV, D)), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, interpret=True)
    oref = ref.reference_attention(q, k, v, causal=causal)
    assert o.shape == oref.shape
    assert float(jnp.abs(o - oref).max()) < 2e-5


@pytest.mark.parametrize("Sc,fill,window", [
    (128, 100, 0), (128, 128, 0), (256, 40, 0), (128, 100, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(Sc, fill, window, dtype):
    B, H, KV, D = 2, 8, 2, 64
    q = jnp.asarray(RNG.standard_normal((B, 1, H, D)), dtype)
    kc = jnp.asarray(RNG.standard_normal((B, Sc, KV, D)), dtype)
    vc = jnp.asarray(RNG.standard_normal((B, Sc, KV, D)), dtype)
    ap = jnp.broadcast_to(jnp.arange(Sc)[None], (B, Sc)).astype(jnp.int32)
    ap = jnp.where(ap < fill, ap, -1)
    pos = jnp.asarray([fill - 1, max(fill // 2, 1)], jnp.int32)
    o = decode_attention(q, kc, vc, ap, pos, window=window, block_k=64,
                         interpret=True)
    oref = ref.decode_attend(q, kc, vc, ap, pos, window=window)
    err = float(jnp.abs(o.astype(jnp.float32)
                        - oref.astype(jnp.float32)).max())
    assert err < tol(dtype), err


@pytest.mark.parametrize("B,T,H,D,chunk", [
    (1, 64, 2, 16, 16), (2, 128, 4, 32, 32), (1, 96, 1, 64, 32),
])
def test_rwkv6_scan_sweep(B, T, H, D, chunk):
    r, k, v = (jnp.asarray(RNG.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.2, 0.99, (B, T, H, D)), jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, D)), jnp.float32)
    s0 = jnp.asarray(RNG.standard_normal((B, H, D, D)), jnp.float32)
    o, sT = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    oref, sTref = ref.rwkv6_ref(r, k, v, w, u, s0)
    assert float(jnp.abs(o - oref).max()) < 5e-4
    assert float(jnp.abs(sT - sTref).max()) < 5e-4


@pytest.mark.parametrize("M,K,N", [(64, 128, 64), (32, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_sweep(M, K, N, dtype):
    x = jnp.asarray(RNG.standard_normal((M, K)), dtype)
    wq = jnp.asarray(RNG.integers(-127, 127, (K, N)), jnp.int8)
    ws = jnp.asarray(RNG.uniform(0.001, 0.01, (N,)), jnp.float32)
    o = int8_matmul(x, wq, ws, block_m=32, block_n=64, block_k=64,
                    interpret=True)
    oref = ref.int8_matmul_ref(x, wq, ws)
    rel = float(jnp.abs(o.astype(jnp.float32) - oref.astype(jnp.float32)
                        ).max() / jnp.abs(oref.astype(jnp.float32)).max())
    assert rel < 5e-3, rel  # kernel accumulates via bf16 MXU passes


@pytest.mark.parametrize("g,V,seed", [(4, 64, 0), (8, 128, 1), (2, 32, 2),
                                      (6, 512, 3)])
def test_spec_verify_matches_oracle(g, V, seed):
    rng = np.random.default_rng(seed)
    dtok = jnp.asarray(rng.integers(0, V, (g,)), jnp.int32)
    dp = jax.nn.softmax(jnp.asarray(rng.standard_normal((g, V)),
                                    jnp.float32), -1)
    tp = jax.nn.softmax(jnp.asarray(rng.standard_normal((g + 1, V)),
                                    jnp.float32), -1)
    key = jax.random.key(seed)
    n1, t1 = spec_verify(dtok, dp, tp, key, interpret=True)
    n2, t2 = ref.spec_verify_ref(dtok, dp, tp, key)
    assert int(n1) == int(n2)
    assert int(t1) == int(t2)


def test_flash_matches_blockwise_cpu_path():
    """The CPU (dry-run) blockwise path and the Pallas kernel must have
    identical semantics: same inputs -> same outputs."""
    from repro.models.attention import flash_causal, flash_windowed
    B, S, H, KV, D = 1, 128, 4, 2, 32
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, D)), jnp.float32)
    a = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    b = flash_causal(q, k, v, block=32)
    assert float(jnp.abs(a - b).max()) < 1e-5
    a = flash_attention(q, k, v, window=40, block_q=32, block_k=32,
                        interpret=True)
    b = flash_windowed(q, k, v, window=40, block=32)
    assert float(jnp.abs(a - b).max()) < 1e-5


def test_semantic_attestation_on_kernels():
    """Paper §6 computation attestation: canonical inputs through the
    accelerator kernel vs the CPU oracle within epsilon."""
    from repro.core.attestation import semantic_attest
    B, S, H, D = 1, 64, 2, 32
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    rep = semantic_attest(
        lambda q, k, v: flash_attention(q, k, v, block_q=32, block_k=32,
                                        interpret=True),
        lambda q, k, v: ref.reference_attention(q, k, v),
        (q, k, v), eps=1e-3)
    assert rep["ok"], rep
