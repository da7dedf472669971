"""Kernel dispatch layer.

Every hot op has (a) a Pallas TPU kernel (``<name>.py``) and (b) a pure
jnp oracle (``ref.py``).  Dispatch policy:

  * TPU backend        -> pallas_call kernel (VMEM-tiled)
  * CPU / dry-run      -> the blockwise jnp implementation in
                          ``models.attention`` (same FLOP profile as the
                          kernel, so §Roofline derived from the CPU-
                          compiled HLO is faithful)
  * ``set_backend("ref")`` -> oracle (tests, the chip smoke's
                          reference run)

``interpret=True`` Pallas execution is reachable via
``set_backend("interpret")`` -- used by the kernel test sweeps on CPU.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.models import attention as attn_ref

_BACKEND_OVERRIDE: str | None = None  # None | "ref" | "pallas" | "interpret"


def set_backend(name: str | None):
    global _BACKEND_OVERRIDE
    _BACKEND_OVERRIDE = name


def backend() -> str:
    if _BACKEND_OVERRIDE:
        return _BACKEND_OVERRIDE
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "jnp_block"


def _pallas_ok() -> bool:
    return backend() in ("pallas", "interpret")


def _interpret() -> bool:
    return backend() == "interpret"


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def _per_device(kernel, q, k, v, mesh, rules):
    """Run a Pallas attention kernel on each device's shard of (q, k, v).

    Mosaic kernels cannot be partitioned automatically, so on a mesh the
    call goes inside a shard_map whose specs follow the activations'
    logical layout.  Heads stay split only where query and kv heads are
    split alike: the kernel maps local query head h to kv head h // G."""
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v)

    def spec(x, heads):
        s = tuple(shd.resolve(("batch", None, heads, None), mesh, x.shape,
                              rules))
        return s + (None,) * (4 - len(s))

    qs, ks = spec(q, "act_heads"), spec(k, "act_kv_heads")
    if qs[2] != ks[2]:
        qs, ks = qs[:1] + (None,) * 3, ks[:1] + (None,) * 3
    return shd.shard_map(kernel, mesh=mesh, in_specs=(P(*qs), P(*ks), P(*ks)),
                         out_specs=P(*qs))(q, k, v)


def _flash(q, k, v, mesh, rules, **kw):
    from repro.kernels import flash_attention as fa
    return _per_device(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=_interpret(),
                                           **kw),
        q, k, v, mesh, rules)


def attention_causal(q, k, v, *, softcap=0.0, block=512, mesh=None,
                     rules=None):
    if _pallas_ok():
        return _flash(q, k, v, mesh, rules, causal=True, window=0,
                      softcap=softcap)
    if backend() == "ref":
        return attn_ref.reference_attention(q, k, v, causal=True,
                                            softcap=softcap)
    return attn_ref.flash_causal(q, k, v, softcap=softcap, block=block)


def attention_windowed(q, k, v, *, window, softcap=0.0, block=512,
                       q_offset=0, mesh=None, rules=None):
    if _pallas_ok():
        return _flash(q, k, v, mesh, rules, causal=True, window=window,
                      softcap=softcap)
    if backend() == "ref":
        return attn_ref.reference_attention(q, k, v, causal=True,
                                            window=window, softcap=softcap,
                                            q_offset=q_offset)
    return attn_ref.flash_windowed(q, k, v, window=window, softcap=softcap,
                                   block=block, q_offset=q_offset)


def attention_full(q, k, v, *, softcap=0.0, block=512, kv_len=None,
                   mesh=None, rules=None):
    if _pallas_ok():
        return _flash(q, k, v, mesh, rules, causal=False, window=0,
                      softcap=softcap)
    if backend() == "ref":
        return attn_ref.reference_attention(q, k, v, causal=False,
                                            softcap=softcap, kv_len=kv_len)
    return attn_ref.flash_full(q, k, v, softcap=softcap, block=block,
                               kv_len=kv_len)


def decode_attention(q, k_cache, v_cache, abs_pos, positions, *,
                     window=0, softcap=0.0):
    if _pallas_ok():
        from repro.kernels import decode_attention as da
        return da.decode_attention(q, k_cache, v_cache, abs_pos, positions,
                                   window=window, softcap=softcap,
                                   interpret=_interpret())
    return attn_ref.decode_attend(q, k_cache, v_cache, abs_pos, positions,
                                  window=window, softcap=softcap)


def paged_decode_attention(q, k_pool, v_pool, page_table, positions, *,
                           page_size, window=0, softcap=0.0):
    """One-token attention over a paged KV pool (see decode_attention.py).

    q: (B,1,H,D); pools: (P, page_size, KV, D); page_table: (B, NP)
    int32, -1 = unmapped; positions: (B,).
    """
    if _pallas_ok():
        from repro.kernels import decode_attention as da
        return da.paged_decode_attention(q, k_pool, v_pool, page_table,
                                         positions, window=window,
                                         softcap=softcap,
                                         interpret=_interpret())
    return attn_ref.paged_decode_attend(q, k_pool, v_pool, page_table,
                                        positions, page_size=page_size,
                                        window=window, softcap=softcap)


# --------------------------------------------------------------------------
# speculative verification
# --------------------------------------------------------------------------

def spec_verify(draft_tokens, draft_probs, target_probs, rng):
    """Token-level speculative-decoding acceptance (see kernels/ref.py)."""
    if _pallas_ok():
        from repro.kernels import spec_verify as sv
        return sv.spec_verify(draft_tokens, draft_probs, target_probs, rng,
                              interpret=_interpret())
    from repro.kernels import ref
    return ref.spec_verify_ref(draft_tokens, draft_probs, target_probs, rng)


# --------------------------------------------------------------------------
# int8 quantized matmul (edge-tier replicas)
# --------------------------------------------------------------------------

def int8_matmul(x, w_q, w_scale):
    if _pallas_ok():
        from repro.kernels import int8_matmul as im
        return im.int8_matmul(x, w_q, w_scale, interpret=_interpret())
    from repro.kernels import ref
    return ref.int8_matmul_ref(x, w_q, w_scale)
