"""Pallas TPU flash-attention kernel (BlockSpec VMEM tiling).

TPU-native design notes (DESIGN.md §3 hardware adaptation):

* Tiles are MXU-aligned: ``block_q x head_dim`` and ``block_k x head_dim``
  with ``head_dim`` padded to a lane multiple (128) by the caller.
* The grid is ``(batch*heads, T/block_q, S/block_k)``; the KV dimension is
  the minor (sequential) axis so the f32 accumulator, running max ``m`` and
  normalizer ``l`` live in VMEM scratch across KV steps — the online-softmax
  recurrence never touches HBM.
* GQA is expressed in the BlockSpec index maps: the K/V index map divides
  the head id by ``rep = H // Hkv``, so query heads of one group stream the
  same KV tiles (VMEM reuse instead of materializing repeated KV).
* Causal / sliding-window masks are applied with ``broadcasted_iota`` over
  absolute positions; fully-masked tiles still execute (documented trade-off
  — grid pruning is a possible follow-up).

**Backward.** ``flash_attention_pallas`` carries a ``jax.custom_vjp``: the
forward is the Pallas kernel, the backward is :func:`flash_attention_bwd`,
an explicit ``jnp`` recomputation blocked over KV (one pass for the
log-sum-exp, one for the gradients), so no ``(T, S)`` score matrix is
ever materialized whole.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_bwd", "flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    q_offset: int,
    block_q: int,
    block_k: int,
    seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * scale  # (block_q, hd)
    k = k_ref[...].astype(jnp.float32)  # (block_k, hd)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_k
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    correction = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * correction + p.sum(axis=-1)
    v = v_ref[...].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * correction[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-37)[:, None]
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int = 0,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas flash attention. Same contract as ``ref.flash_attention_ref``.

    ``q: (B, T, H, hd)``, ``k/v: (B, S, Hkv, hd)`` with ``H % Hkv == 0``.
    Differentiable: the backward is :func:`flash_attention_bwd`.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    opts = (causal, q_offset, window, softcap, scale, block_q, block_k, interpret)
    return _flash_vjp(q, k, v, opts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_vjp(q, k, v, opts):
    return _flash_forward(q, k, v, *opts)


def _flash_vjp_fwd(q, k, v, opts):
    out = _flash_forward(q, k, v, *opts)
    return out, (q, k, v, out)


def _flash_vjp_bwd(opts, residuals, d_out):
    causal, q_offset, window, softcap, scale, _bq, block_k, _interp = opts
    q, k, v, out = residuals
    return flash_attention_bwd(
        q, k, v, out, d_out, causal=causal, q_offset=q_offset, window=window,
        softcap=softcap, scale=scale, block_k=block_k,
    )


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_forward(q, k, v, causal, q_offset, window, softcap, scale,
                   block_q, block_k, interpret):
    b, t, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv

    block_q = min(block_q, t)
    block_k = min(block_k, s)
    pad_q = (-t) % block_q
    pad_k = (-s) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    tq, sk = qp.shape[1], kp.shape[1]

    # (B*H, T, hd) query-major layout; KV stays (B*Hkv, S, hd).
    q3 = qp.transpose(0, 2, 1, 3).reshape(b * h, tq, hd)
    k3 = kp.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    v3 = vp.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)

    grid = (b * h, tq // block_q, sk // block_k)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        seq_k=s,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, hd), lambda bh, qi, ki, rep=rep: (bh // rep, ki, 0)),
            pl.BlockSpec((None, block_k, hd), lambda bh, qi, ki, rep=rep: (bh // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    out = out.reshape(b, h, tq, hd).transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :t]
    return out


def flash_attention_bwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    out: jnp.ndarray,
    d_out: jnp.ndarray,
    *,
    causal: bool,
    q_offset: int,
    window: Optional[int],
    softcap: Optional[float],
    scale: float,
    block_k: int = 128,
):
    """Gradients ``(dq, dk, dv)`` of flash attention, recomputed in ``jnp``.

    The FlashAttention-2 backward over KV blocks of ``block_k``: a first
    scan rebuilds each query's log-sum-exp, a second one forms the
    probabilities block by block and accumulates ``dq`` while emitting
    each block's ``dk``/``dv``. ``out`` is the forward output (for
    ``D = rowsum(dO * O)``). All math is f32; results take the input dtypes.
    """
    b, t, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv
    blk = min(block_k, s)
    pad = (-s) % blk
    n_blocks = (s + pad) // blk

    qf = q.astype(jnp.float32).reshape(b, t, hkv, rep, hd) * scale
    do = d_out.astype(jnp.float32).reshape(b, t, hkv, rep, hd)
    delta = jnp.sum(do * out.astype(jnp.float32).reshape(b, t, hkv, rep, hd), -1)

    def blocks(x):
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(b, n_blocks, blk, hkv, hd).transpose(1, 0, 2, 3, 4)

    kb, vb = blocks(k), blocks(v)
    q_pos = q_offset + jnp.arange(t)

    def scores(k_blk, i):
        raw = jnp.einsum("bthrd,bshd->bthrs", qf, k_blk)
        capped = raw if softcap is None else jnp.tanh(raw / softcap) * softcap
        k_pos = i * blk + jnp.arange(blk)
        mask = k_pos[None, :] < s
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        mask = mask[None, :, None, None, :]  # (1, T, 1, 1, blk)
        return raw, jnp.where(mask, capped, -jnp.inf), mask

    def lse_step(carry, xs):
        m, l = carry
        k_blk, i = xs
        _, sc, _ = scores(k_blk, i)
        m_new = jnp.maximum(m, jnp.max(sc, -1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        l = l * jnp.exp(m - m_safe) + jnp.sum(jnp.exp(sc - m_safe[..., None]), -1)
        return (m_new, l), None

    m0 = jnp.full((b, t, hkv, rep), -jnp.inf, jnp.float32)
    (m, l), _ = jax.lax.scan(
        lse_step, (m0, jnp.zeros_like(m0)), (kb, jnp.arange(n_blocks))
    )
    lse = jnp.where(l > 0, jnp.where(jnp.isneginf(m), 0.0, m) + jnp.log(l), jnp.inf)

    def grad_step(dq, xs):
        k_blk, v_blk, i = xs
        raw, sc, mask = scores(k_blk, i)
        p = jnp.where(mask, jnp.exp(sc - lse[..., None]), 0.0)
        dv = jnp.einsum("bthrs,bthrd->bshd", p, do)
        dp = jnp.einsum("bthrd,bshd->bthrs", do, v_blk)
        ds = p * (dp - delta[..., None])
        if softcap is not None:
            ds = ds * (1.0 - jnp.square(jnp.tanh(raw / softcap)))
        dq = dq + jnp.einsum("bthrs,bshd->bthrd", ds, k_blk)
        dk = jnp.einsum("bthrs,bthrd->bshd", ds, qf)
        return dq, (dk, dv)

    dq, (dk, dv) = jax.lax.scan(
        grad_step, jnp.zeros_like(qf), (kb, vb, jnp.arange(n_blocks))
    )

    def unblock(x):
        return x.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * blk, hkv, hd)[:, :s]

    dq = (dq * scale).reshape(b, t, h, hd).astype(q.dtype)
    return dq, unblock(dk).astype(k.dtype), unblock(dv).astype(v.dtype)
