"""GQA attention layer: projections, rotary, flash core, KV-cache decode.

Three entry modes share weights:
* ``attn_forward``  — full-sequence (train / prefill), flash-attention core.
* ``attn_decode``   — single-token step against a KV cache (einsum; decode
  is memory-bound, flash brings nothing at q_len=1).
* cross-attention (whisper decoder) via ``attn_forward(kv_override=...)``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ModelConfig
from ..kernels import ops
from .layers import apply_mrope, apply_rope, dense_init, rmsnorm, rmsnorm_init, soft_cap

__all__ = ["attn_init", "attn_forward", "attn_decode", "init_kv_cache"]


def attn_init(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    params = {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.use_qk_norm:
        params["q_norm"] = rmsnorm_init(hd, dtype)
        params["k_norm"] = rmsnorm_init(hd, dtype)
    del cross  # same shapes for cross-attention
    return params


def _project_qkv(params, cfg: ModelConfig, x, kv_src):
    b, t, _ = x.shape
    s = kv_src.shape[1]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("btd,dk->btk", x, params["wq"]).reshape(b, t, h, hd)
    k = jnp.einsum("bsd,dk->bsk", kv_src, params["wk"]).reshape(b, s, hkv, hd)
    v = jnp.einsum("bsd,dk->bsk", kv_src, params["wv"]).reshape(b, s, hkv, hd)
    if cfg.use_qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, params["k_norm"], cfg.rms_eps)
    return q, k, v


def _rotary(cfg: ModelConfig, q, k, positions):
    if positions is None:
        return q, k
    if cfg.use_mrope and positions.ndim == 3:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


@obs.scoped(obs.ATTN)
def attn_forward(
    params: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: Optional[jnp.ndarray],
    *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_override: Optional[jnp.ndarray] = None,
    return_kv: bool = False,
):
    """Full-sequence attention. ``x: (B, T, D)``.

    ``kv_override`` switches to cross-attention against the given memory
    (whisper decoder). ``return_kv`` also returns (k, v) for cache priming.
    """
    kv_src = x if kv_override is None else kv_override
    q, k, v = _project_qkv(params, cfg, x, kv_src)
    if kv_override is None:
        q, k = _rotary(cfg, q, k, positions)
    out = ops.flash_attention(
        q,
        k,
        v,
        causal=causal and kv_override is None,
        window=window,
        softcap=cfg.attn_logit_softcap,
    )
    b, t = x.shape[:2]
    out = jnp.einsum(
        "btk,kd->btd", out.reshape(b, t, cfg.num_heads * cfg.head_dim), params["wo"]
    )
    if return_kv:
        return out, (k, v)
    return out


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, hkv, hd), dtype=dtype),
        "v": jnp.zeros((batch, max_len, hkv, hd), dtype=dtype),
    }


@obs.scoped(obs.ATTN)
def attn_decode(
    params: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    cache: dict,
    pos: jnp.ndarray,
    *,
    window: Optional[int] = None,
    kv_override_cache: Optional[dict] = None,
):
    """One-token decode. ``x: (B, 1, D)``, ``pos``: scalar current position.

    Returns ``(out, new_cache)``. With ``kv_override_cache`` (cross-attn
    pre-computed memory) the cache is static and returned unchanged.
    """
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if kv_override_cache is not None:
        k, v = kv_override_cache["k"], kv_override_cache["v"]
        q = jnp.einsum("btd,dk->btk", x, params["wq"]).reshape(b, 1, h, hd)
        if cfg.use_qk_norm:
            q = rmsnorm(q, params["q_norm"], cfg.rms_eps)
        out = _decode_core(q, k, v, None, cfg, s_valid=k.shape[1])
        out = jnp.einsum("btk,kd->btd", out.reshape(b, 1, h * hd), params["wo"])
        return out, kv_override_cache

    q, k_new, v_new = _project_qkv(params, cfg, x, x)
    pos_arr = jnp.full((b, 1), pos, dtype=jnp.int32)
    if cfg.use_mrope:
        pos3 = jnp.broadcast_to(pos_arr[:, None, :], (b, 3, 1))
        q, k_new = _rotary(cfg, q, k_new, pos3)
    else:
        q, k_new = _rotary(cfg, q, k_new, pos_arr)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, pos, 0, 0))
    out = _decode_core(q, k, v, pos, cfg, s_valid=None, window=window)
    out = jnp.einsum("btk,kd->btd", out.reshape(b, 1, h * hd), params["wo"])
    return out, {"k": k, "v": v}


def _decode_core(q, k, v, pos, cfg: ModelConfig, s_valid, window=None):
    """Einsum attention for q_len=1 with position masking over the cache."""
    b, _, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, hd) * hd**-0.5
    scores = jnp.einsum("bhrd,bshd->bhrs", qf, k.astype(jnp.float32))
    scores = soft_cap(scores, cfg.attn_logit_softcap)
    k_pos = jnp.arange(s)
    if pos is not None:
        mask = k_pos <= pos
        if window is not None:
            mask = mask & (pos - k_pos < window)
    else:
        mask = k_pos < (s if s_valid is None else s_valid)
    scores = jnp.where(mask[None, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrs,bshd->bhrd", p, v.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)
