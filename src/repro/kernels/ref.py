"""Pure-jnp oracles for every Pallas kernel (and the CPU execution path).

Each function here is the semantic ground truth: the Pallas kernels in this
package must match these to float tolerance (tests sweep shapes/dtypes in
``interpret=True``), and non-TPU backends execute these directly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "grouped_matmul_ref", "rmsnorm_ref", "routed_expert_ffn_ref"]


def _soft_cap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def flash_attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_k: int = 512,
) -> jnp.ndarray:
    """Blockwise online-softmax attention (FlashAttention semantics).

    Args:
      q: ``(B, T, H, hd)`` queries.
      k: ``(B, S, Hkv, hd)`` keys (GQA: ``H % Hkv == 0``).
      v: ``(B, S, Hkv, hd)`` values.
      causal: causal masking using absolute positions ``q_pos = q_offset + t``.
      q_offset: absolute position of the first query (decode: ``S - T``).
      window: sliding-window size (None = unlimited). A key at position
        ``p`` is visible iff ``q_pos - p < window`` (and ``p <= q_pos``).
      softcap: attention-logit soft cap (gemma2): ``tanh(x/c) * c``.
      scale: score scale (default ``hd ** -0.5``).
      block_k: KV block length for the scan (memory control).

    Returns ``(B, T, H, hd)`` in the dtype of ``q``.
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv
    if scale is None:
        scale = hd**-0.5
    orig_dtype = q.dtype
    qf = q.astype(jnp.float32).reshape(b, t, hkv, rep, hd) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    blk = min(block_k, s)
    pad = (-s) % blk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = kf.shape[1] // blk
    kf = kf.reshape(b, n_blocks, blk, hkv, hd)
    vf = vf.reshape(b, n_blocks, blk, hkv, hd)

    q_pos = q_offset + jnp.arange(t)  # (T,)

    def body(carry, inputs):
        m_prev, l_prev, acc_prev = carry
        k_blk, v_blk, blk_idx = inputs  # (B, blk, Hkv, hd) x2, scalar
        scores = jnp.einsum("bthrd,bshd->bhrts", qf, k_blk)  # (B,Hkv,rep,T,blk)
        scores = _soft_cap(scores, softcap)
        k_pos = blk_idx * blk + jnp.arange(blk)  # (blk,)
        mask = k_pos[None, :] < s  # padding
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        m_cur = jnp.max(scores, axis=-1)  # (B,Hkv,rep,T)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows (m == -inf) against NaNs.
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        correction = jnp.where(
            jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe)
        )
        l_new = l_prev * correction + p.sum(axis=-1)
        acc_new = acc_prev * correction.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
            "bhrts,bshd->bthrd", p, v_blk
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, rep, t), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, t), dtype=jnp.float32)
    acc0 = jnp.zeros((b, t, hkv, rep, hd), dtype=jnp.float32)
    (m_f, l_f, acc_f), _ = jax.lax.scan(
        body,
        (m0, l0, acc0),
        (kf.transpose(1, 0, 2, 3, 4), vf.transpose(1, 0, 2, 3, 4), jnp.arange(n_blocks)),
    )
    l_t = l_f.transpose(0, 3, 1, 2)[..., None]  # (B,T,Hkv,rep,1)
    out = acc_f / jnp.maximum(l_t, 1e-37)
    return out.reshape(b, t, h, hd).astype(orig_dtype)


def grouped_matmul_ref(
    x: jnp.ndarray, w: jnp.ndarray, *, preferred_dtype=jnp.float32
) -> jnp.ndarray:
    """Per-group GEMM: ``(G, N, K) @ (G, K, M) -> (G, N, M)``.

    The MoE expert-FFN hot loop: group g is expert g's token bucket.
    """
    out = jnp.einsum("gnk,gkm->gnm", x, w, preferred_element_type=preferred_dtype)
    return out.astype(x.dtype)


def rmsnorm_ref(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm in fp32 accumulation: ``x * rsqrt(mean(x^2)+eps) * w``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


def routed_expert_ffn_ref(
    x: jnp.ndarray,
    gates: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    act: str = "silu",
) -> jnp.ndarray:
    """Gate-weighted sum of every expert's FFN for every token.

    ``x: (n, d)``, ``gates: (n, E)`` (zero where a token does not route to
    the expert), ``w_gate``/``w_up: (E, d, f)``, ``w_down: (E, f, d)``
    -> ``(n, d)``. The decode-sized MoE: an expert whose gate weight is
    zero for every token adds nothing, though its weights are read.
    """
    gate_h = jnp.einsum("nd,edf->nef", x, w_gate)
    up_h = jnp.einsum("nd,edf->nef", x, w_up)
    a = jax.nn.silu(gate_h) if act == "silu" else jax.nn.gelu(gate_h)
    ye = jnp.einsum("nef,efd->ned", a * up_h, w_down)
    return jnp.einsum("ned,ne->nd", ye, gates)
