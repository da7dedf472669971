"""Per-layer building blocks: norms, RoPE, MLP, the attention module with
KV-cache management, and the layer dispatcher used by the block scanner.

Cache convention (one dict per attention layer):
  k, v     : (B, S_c, KV, Dh)   S_c = window for "local", seq budget else
  abs_pos  : (B, S_c) int32     absolute position held by each slot (-1 empty)
Local layers ring-buffer by ``abs_pos % window``; global layers index by
absolute position.  RoPE is applied before caching (standard practice),
so migration/restore needs no position rebasing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LayerSpec, ModelConfig
from repro import sharding as shd
from repro.models import attention as attn_ref
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod


def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    n = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (n * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta=10000.0):
    """x: (B, S, H, D), positions: (B, S) absolute."""
    D = x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def mlp_apply(p, x, cfg: ModelConfig):
    g = jnp.einsum("btd,df->btf", x, p["w_gate"])
    u = jnp.einsum("btd,df->btf", x, p["w_up"])
    act = jax.nn.gelu(g) if cfg.act == "gelu" else jax.nn.silu(g)
    return jnp.einsum("btf,fd->btd", act * u, p["w_down"])


# ---------------------------------------------------------------------------
# attention module
# ---------------------------------------------------------------------------

def make_attn_cache(cfg: ModelConfig, lspec: LayerSpec, batch: int,
                    max_len: int, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    S_c = min(lspec.window, max_len) if lspec.mixer == "local" else max_len
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, S_c, KV, Dh), dtype),
        "v": jnp.zeros((batch, S_c, KV, Dh), dtype),
        "abs_pos": jnp.full((batch, S_c), -1, jnp.int32),
    }


def _cache_slots(lspec: LayerSpec, S_c: int, positions):
    """Map absolute positions (B,S) -> cache slot indices."""
    if lspec.mixer == "local":
        return positions % S_c
    return jnp.minimum(positions, S_c - 1)


def _write_cache(cache, lspec, k, v, positions):
    """Scatter k/v (B,S,KV,Dh) at ``positions`` (B,S) into the cache."""
    S_c = cache["k"].shape[1]
    slots = _cache_slots(lspec, S_c, positions)

    def upd(buf, val, slot):  # per-batch scatter over slot axis
        return buf.at[slot].set(val, mode="drop")

    new = dict(cache)
    new["k"] = jax.vmap(upd)(cache["k"], k, slots)
    new["v"] = jax.vmap(upd)(cache["v"], v, slots)
    new["abs_pos"] = jax.vmap(upd)(cache["abs_pos"], positions, slots)
    return new


def make_paged_attn_cache(cfg: ModelConfig, pages: int, page_size: int,
                          dtype=None) -> dict:
    """Shared KV page pools for one attention layer.

    Unlike the dense per-row cache there is no batch axis: every batch
    row's pages live in one (pages, page_size, KV, Dh) pool and rows
    address it through a (B, NP) page table woven in as
    ``cache["page_table"]`` before the forward pass.  Local
    (sliding-window) layers use the same full logical layout -- the
    window is enforced by the attend mask, not by a ring buffer --
    which keeps one write rule for every attn layer.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k_pool": jnp.zeros((pages, page_size, KV, Dh), dtype),
        "v_pool": jnp.zeros((pages, page_size, KV, Dh), dtype),
    }


def _write_pages(cache, k, v, positions):
    """Scatter k/v (B,S,KV,Dh) at absolute ``positions`` (B,S) into the
    shared page pools through ``cache["page_table"]`` (B,NP).

    Rows whose page entry is -1 (dead/inactive) aim at the
    out-of-bounds sentinel index P so the write drops -- never a
    negative index, which would wrap instead of dropping.  Returns pools
    only (no page_table): the master table lives in the engine state.
    """
    P, ps = cache["k_pool"].shape[0], cache["k_pool"].shape[1]
    pt = cache["page_table"]
    page = jnp.take_along_axis(pt, positions // ps, axis=1)   # (B, S)
    page = jnp.where(page < 0, P, page)
    off = positions % ps
    kv_shape = k.shape[2:]
    page2, off2 = page.reshape(-1), off.reshape(-1)
    k2 = k.reshape((-1,) + kv_shape)
    v2 = v.reshape((-1,) + kv_shape)
    return {
        "k_pool": cache["k_pool"].at[page2, off2].set(k2, mode="drop"),
        "v_pool": cache["v_pool"].at[page2, off2].set(v2, mode="drop"),
    }


def attention_apply(p, x, *, cfg: ModelConfig, lspec: LayerSpec, mode: str,
                    positions, cache=None, mesh=None, rules=None,
                    kv_x=None, causal=True, cross=False):
    """Returns (out (B,S,d), new_cache | None).

    mode: "train" | "prefill" | "decode".  ``cross=True`` switches the
    module into cross-attention: keys/values come from ``kv_x`` (the
    encoder sequence) and are cached once at prefill; at decode the
    cached cross K/V are reused (kv_x may then be None).
    """
    B, S, d = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = lspec.window if lspec.mixer == "local" else 0
    cross = cross or kv_x is not None

    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if mode == "decode" and cross:
        # cross K/V precomputed at prefill; just attend
        o = attn_ref.decode_attend(q, cache["k"], cache["v"],
                                   cache["abs_pos"],
                                   jnp.full((B,), 1 << 30, jnp.int32),
                                   window=0, softcap=cfg.attn_softcap)
        out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
        return out, cache

    src = kv_x if cross else x
    k = jnp.einsum("btd,dhk->bthk", src, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", src, p["wv"])
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if not cross:  # cross-attention keys are position-free (whisper style)
        kv_pos = positions if not cross else None
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_pos, cfg.rope_theta)
    if mesh is not None:
        q = shd.constrain(q, mesh, ("batch", None, "act_heads", None), rules)
        k = shd.constrain(k, mesh, ("batch", None, "act_kv_heads", None), rules)
        v = shd.constrain(v, mesh, ("batch", None, "act_kv_heads", None), rules)

    if mode == "decode":
        if "k_pool" in cache:
            # paged path: single-token steps only (wide verify windows
            # stay on the dense path)
            from repro.kernels import ops as kops
            new_cache = _write_pages(cache, k, v, positions)
            o = kops.paged_decode_attention(
                q, new_cache["k_pool"], new_cache["v_pool"],
                cache["page_table"], positions[:, 0],
                page_size=cache["k_pool"].shape[1],
                window=window, softcap=cfg.attn_softcap)
        else:
            new_cache = _write_cache(cache, lspec, k, v, positions)
            # positions ride through whole: one column is the classic
            # single-token step; S>1 columns are a speculative verify
            # window where every query carries its own causal horizon
            o = attn_ref.decode_attend(q, new_cache["k"], new_cache["v"],
                                       new_cache["abs_pos"], positions,
                                       window=window,
                                       softcap=cfg.attn_softcap)
    else:
        from repro.kernels import ops as kops
        on = dict(softcap=cfg.attn_softcap, mesh=mesh, rules=rules)
        if cross or not causal:  # cross / encoder self-attention
            o = kops.attention_full(q, k, v, **on)
        elif window:
            o = kops.attention_windowed(q, k, v, window=window, **on)
        else:
            o = kops.attention_causal(q, k, v, **on)
        new_cache = None
        if mode == "prefill" and cache is not None:
            if cross:
                # cache the encoder K/V once; abs_pos marks validity
                pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None],
                                       (B, k.shape[1]))
                new_cache = _write_cache(cache, lspec, k, v, pos)
            elif "k_pool" in cache:
                new_cache = _write_pages(cache, k, v, positions)
            else:
                new_cache = _write_cache(cache, lspec, k, v, positions)

    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    if mesh is not None:
        out = shd.constrain(out, mesh, ("batch", None, "embed"), rules)
    return out, new_cache


# ---------------------------------------------------------------------------
# ssm caches
# ---------------------------------------------------------------------------

def make_rwkv_cache(cfg: ModelConfig, batch: int) -> dict:
    H, Dh = cfg.rwkv_heads, cfg.rwkv_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "state": jnp.zeros((batch, H, Dh, Dh), jnp.float32),
        "x_tm": jnp.zeros((batch, cfg.d_model), dt),
        "x_cm": jnp.zeros((batch, cfg.d_model), dt),
    }


def make_mamba_cache(cfg: ModelConfig, batch: int) -> dict:
    return {
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.mamba_d_state),
                         jnp.float32),
        "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.d_inner),
                          jnp.dtype(cfg.dtype)),
    }


def make_layer_cache(cfg: ModelConfig, lspec: LayerSpec, batch: int,
                     max_len: int, cross: bool = False,
                     cross_len: int = 0) -> dict:
    c: dict = {}
    if lspec.mixer in ("attn", "local"):
        c["attn"] = make_attn_cache(cfg, lspec, batch, max_len)
    elif lspec.mixer == "rwkv":
        c["rwkv"] = make_rwkv_cache(cfg, batch)
    elif lspec.mixer == "mamba":
        c["mamba"] = make_mamba_cache(cfg, batch)
    if cross:
        c["cross"] = make_attn_cache(
            cfg, LayerSpec(mixer="attn", ffn="none"), batch, cross_len)
    return c


# ---------------------------------------------------------------------------
# layer dispatch (pre-norm residual transformer convention)
# ---------------------------------------------------------------------------

def layer_apply(p, x, *, cfg: ModelConfig, lspec: LayerSpec, mode: str,
                positions, cache=None, mesh=None, rules=None, enc_out=None,
                causal=True):
    """One full layer (mixer + optional cross-attn + ffn).

    Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    c = cache or {}

    if lspec.mixer in ("attn", "local"):
        h = rmsnorm(x, p["attn"]["ln"]["scale"], cfg.norm_eps)
        h, nc = attention_apply(
            p["attn"], h, cfg=cfg, lspec=lspec, mode=mode,
            positions=positions, cache=c.get("attn"), mesh=mesh,
            rules=rules, causal=causal)
        x = x + h
        if nc is not None:
            new_cache["attn"] = nc
    elif lspec.mixer == "rwkv":
        h = rmsnorm(x, p["rwkv"]["ln"]["scale"], cfg.norm_eps)
        rc = c.get("rwkv")
        if mode == "decode":
            h, st, xl = rwkv_mod.timemix_step(
                p["rwkv"], h, cfg, state=rc["state"],
                x_last=rc["x_tm"].astype(h.dtype))
        else:
            # chunk=8 is required only for backward stability (decay
            # division, see rwkv6.py); forward-only modes take 64 for
            # 8x fewer state round-trips
            h, st, xl = rwkv_mod.timemix_parallel(
                p["rwkv"], h, cfg,
                state=rc["state"] if rc else None,
                x_last=rc["x_tm"].astype(h.dtype) if rc else None,
                mesh=mesh, rules=rules,
                chunk=8 if mode == "train" else 64)
        x = x + h
        if mode != "train":
            cdt = jnp.dtype(cfg.dtype)
            new_cache["rwkv"] = {"state": st,
                                 "x_tm": xl.astype(cdt),
                                 "x_cm": (rc or {}).get(
                                     "x_cm",
                                     jnp.zeros_like(xl).astype(cdt))}
    elif lspec.mixer == "mamba":
        h = rmsnorm(x, p["mamba"]["ln"]["scale"], cfg.norm_eps)
        mc = c.get("mamba")
        if mode == "decode":
            h, st, tail = mamba_mod.mamba_step(
                p["mamba"], h, cfg, state=mc["ssm"],
                conv_tail=mc["conv"])
        else:
            h, st, tail = mamba_mod.mamba_parallel(
                p["mamba"], h, cfg,
                state=mc["ssm"] if mc else None,
                conv_tail=mc["conv"] if mc else None,
                mesh=mesh, rules=rules)
        x = x + h
        if mode != "train":
            new_cache["mamba"] = {"ssm": st, "conv": tail}

    if "cross" in p and (enc_out is not None or mode == "decode"):
        h = rmsnorm(x, p["cross"]["ln"]["scale"], cfg.norm_eps)
        h, nc = attention_apply(
            p["cross"], h, cfg=cfg, lspec=LayerSpec("attn", "none"),
            mode=mode, positions=positions, cache=c.get("cross"),
            mesh=mesh, rules=rules, kv_x=enc_out, cross=True)
        x = x + h
        if nc is not None:
            new_cache["cross"] = nc

    if lspec.ffn == "dense":
        if lspec.mixer == "rwkv":
            h = rmsnorm(x, p["mlp"]["ln"]["scale"], cfg.norm_eps)
            xcm = (c.get("rwkv") or {}).get("x_cm")
            h, xl = rwkv_mod.channelmix(
                p["mlp"], h,
                x_last=xcm.astype(h.dtype) if xcm is not None else None)
            if mode != "train" and "rwkv" in new_cache:
                new_cache["rwkv"]["x_cm"] = xl.astype(jnp.dtype(cfg.dtype))
        else:
            h = rmsnorm(x, p["mlp"]["ln"]["scale"], cfg.norm_eps)
            h = mlp_apply(p["mlp"], h, cfg)
        x = x + h
    elif lspec.ffn == "moe":
        h = rmsnorm(x, p["moe"]["ln"]["scale"], cfg.norm_eps)
        h, aux = moe_mod.moe_apply(p["moe"], h, cfg, mesh)
        x = x + h

    if mesh is not None:
        x = shd.constrain(x, mesh, ("batch", None, "embed"), rules)
    return x, new_cache, aux
