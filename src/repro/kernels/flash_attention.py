"""Pallas TPU flash-attention kernel (BlockSpec VMEM tiling).

TPU-native design notes (DESIGN.md §3 hardware adaptation):

* **One GQA group per grid step.** The grid is ``(batch*kv_heads,
  T/block_q, S/block_k)``. A query block holds the ``rep = H // Hkv``
  heads that share one KV head (block ``(rep, block_q, hd)`` of the
  ``(B*H, T, hd)`` layout), seen in the kernel as ``rep*block_q`` rows
  against one KV tile: each KV tile is read once for the whole group and
  the MXU gets a tall left-hand side. The KV dimension is the minor
  (sequential) axis, so the f32 accumulator, running max ``m`` and
  normalizer ``l`` live in VMEM scratch across KV steps.
* **Tiles from the shape.** Unless the caller passes ``block_q`` /
  ``block_k``, a step takes up to ``KV_ROWS`` keys and at most
  ``QUERY_ROWS`` query rows: the largest power of two of positions of
  each head of the group that fits, so the block divides power-of-two
  sequences and stays a multiple of ``SUBLANES``, clipped to the sequence
  (:func:`flash_tiles`).
* **MXU operands in the inputs' dtype.** ``QK^T`` takes the q and k tiles
  as they arrive (bf16 on the training path) with f32 accumulation, and
  the scale is applied to the f32 scores; ``m``, ``l``, the mask and
  ``exp`` stay in f32. For ``PV`` the f32 ``P`` is split into ``P_hi``
  (``P`` rounded to ``v``'s dtype) and ``P_lo`` (the remainder, in the
  same dtype); both products accumulate in f32, so ``P`` keeps about 16
  bits. A single cast keeps 8, and its error does not cancel against
  ``l``, which is summed from the f32 ``P``: in training it moved the
  loss against a float32 reference measurably. Float32 inputs keep
  float32 operands and one product.
* **Only tiles with an unmasked pair run.** Under ``causal``, ``window``
  and ``q_offset`` each query tile needs a contiguous range of KV tiles
  (:func:`flash_tile_plan`). Steps outside it skip the body (``pl.when``),
  and their K/V index map repeats the nearest needed tile's block index,
  so Pallas issues no DMA for them. The ``broadcasted_iota`` mask runs
  only on tiles that cross the mask's edge.

**Backward.** ``flash_attention_pallas`` carries a ``jax.custom_vjp``: the
forward is the Pallas kernel, the backward is :func:`flash_attention_bwd`,
an explicit ``jnp`` recomputation blocked over KV (one pass for the
log-sum-exp, one for the gradients), so no ``(T, S)`` score matrix is
ever materialized whole.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_bwd", "flash_attention_pallas", "flash_tile_plan", "flash_tiles"]

NEG_INF = -1e30
LANES = 128
#: Query rows (all heads of a GQA group) and keys per grid step by default.
QUERY_ROWS = 1024
KV_ROWS = 512
#: Query blocks are a multiple of this (bf16 packs 16 rows per tile), so
#: the group's heads stack into rows without a relayout.
SUBLANES = 16
#: KV block of the backward's ``jnp`` scan when the forward's is shape-chosen.
BWD_BLOCK_K = 128


def flash_tiles(t: int, s: int, rep: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> tuple[int, int]:
    """``(block_q, block_k)`` of the forward for ``t`` queries of ``rep``
    heads a group against ``s`` keys. Unless given, a step takes the
    largest power of two of positions of each head whose ``rep`` heads fill
    at most ``QUERY_ROWS`` rows (at least ``SUBLANES``), and ``KV_ROWS``
    keys; either is clipped to its sequence, ``block_q`` to a multiple of
    ``SUBLANES``."""
    if block_q is None:
        block_q = 1 << (max(QUERY_ROWS // rep, SUBLANES).bit_length() - 1)
    block_k = KV_ROWS if block_k is None else block_k
    return min(block_q, -(-t // SUBLANES) * SUBLANES), min(block_k, s)


def _kv_span(qi, *, t, seq_k, block_q, block_k, causal, window, q_offset):
    """First and last KV tile holding an unmasked pair for query tile
    ``qi`` (a Python int, or a grid index inside the kernel)."""
    first, last = 0, (seq_k - 1) // block_k
    if causal:
        last_q = q_offset + jnp.minimum((qi + 1) * block_q, t) - 1
        last = jnp.minimum(last_q, seq_k - 1) // block_k
    if window is not None:
        first = jnp.maximum(q_offset + qi * block_q - window + 1, 0) // block_k
    return first, last


def _tile_unmasked(qi, ki, *, t, seq_k, block_q, block_k, causal, window, q_offset):
    """Whether every pair of tile ``(qi, ki)`` is visible (real query rows)."""
    ok = (ki + 1) * block_k <= seq_k
    if causal:
        ok = ok & ((ki + 1) * block_k - 1 <= q_offset + qi * block_q)
    if window is not None:
        last_q = q_offset + jnp.minimum((qi + 1) * block_q, t) - 1
        ok = ok & (last_q - ki * block_k < window)
    return ok


def _kv_index_map(**span):
    """K/V block index of grid step ``(g, qi, ki)``: ``ki`` clamped to the
    tiles query tile ``qi`` needs, so a skipped step repeats a needed
    tile's index and Pallas fetches nothing for it."""
    def index(g, qi, ki):
        first, last = _kv_span(qi, **span)
        return g, jnp.minimum(jnp.maximum(ki, first), last), 0

    return index


class TilePlan(NamedTuple):
    """Which KV tiles each query tile needs; ``computed`` of ``total``
    grid steps (per batch row and KV head) run the kernel's body."""

    first: tuple[int, ...]
    last: tuple[int, ...]
    computed: int
    total: int


def flash_tile_plan(t: int, s: int, block_q: int, block_k: int, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0) -> TilePlan:
    """The forward kernel's tile plan for ``t`` queries against ``s`` keys."""
    n_q = -(-t // block_q)
    span = dict(t=t, seq_k=s, block_q=block_q, block_k=block_k, causal=causal,
                window=window, q_offset=q_offset)
    first, last = zip(*((int(a), int(b)) for a, b in (_kv_span(qi, **span) for qi in range(n_q))))
    computed = sum(max(b - a + 1, 0) for a, b in zip(first, last))
    return TilePlan(first, last, computed, n_q * -(-s // block_k))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, softcap: Optional[float], span: dict):
    seq_k, block_q, block_k = span["seq_k"], span["block_q"], span["block_k"]
    causal, window, q_offset = span["causal"], span["window"], span["q_offset"]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    rows, hd = acc_scr.shape
    first, last = _kv_span(qi, **span)
    needed = (ki >= first) & (ki <= last)
    unmasked = _tile_unmasked(qi, ki, **span)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked: bool):
        q = q_ref[...].reshape(rows, hd)  # the group's heads stacked
        s = jax.lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        if masked:
            # q_pos - k_pos over the tile; row r is position r % block_q.
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % block_q
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
            gap = (q_offset + qi * block_q - ki * block_k) + row - col
            visible = []
            if seq_k % block_k:
                visible.append(col < seq_k - ki * block_k)
            if causal:
                visible.append(gap >= 0)
            if window is not None:
                visible.append(gap < window)
            mask = functools.reduce(jnp.logical_and, visible)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]  # (rows, LANES), lanes equal
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        if masked:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[...]
        p_hi = p.astype(v.dtype)
        pv = jnp.dot(p_hi, v, preferred_element_type=jnp.float32)
        if v.dtype != jnp.float32:
            # P's rounding to v's dtype, carried by a second MXU product.
            p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
            pv = pv + jnp.dot(p_lo, v, preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv

    pl.when(needed & unmasked)(lambda: body(False))
    if causal or window is not None or seq_k % block_k:
        pl.when(needed & ~unmasked)(lambda: body(True))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-37)[:, :1]
        o_ref[...] = (acc_scr[...] / denom).reshape(o_ref.shape).astype(o_ref.dtype)


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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas flash attention. Same contract as ``ref.flash_attention_ref``.

    ``q: (B, T, H, hd)``, ``k/v: (B, S, Hkv, hd)`` with ``H % Hkv == 0``.
    ``block_q`` / ``block_k`` override the shape-chosen tiles
    (:func:`flash_tiles`). Differentiable: the backward is
    :func:`flash_attention_bwd`, whose KV block is ``block_k`` when given,
    else ``BWD_BLOCK_K``.
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
        softcap=softcap, scale=scale, block_k=BWD_BLOCK_K if block_k is None else block_k,
    )


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_forward(q, k, v, causal, q_offset, window, softcap, scale,
                   block_q, block_k, interpret):
    b, t, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv

    block_q, block_k = flash_tiles(t, s, rep, block_q, block_k)
    pad_q = (-t) % block_q
    pad_k = (-s) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    tq, sk = qp.shape[1], kp.shape[1]

    # (B*H, T, hd) query-major layout, the heads of a GQA group adjacent;
    # KV stays (B*Hkv, S, hd).
    q3 = qp.transpose(0, 2, 1, 3).reshape(b * h, tq, hd)
    k3 = kp.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    v3 = vp.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)

    grid = (b * hkv, tq // block_q, sk // block_k)
    span = dict(t=t, seq_k=s, block_q=block_q, block_k=block_k, causal=causal,
                window=window, q_offset=q_offset)
    kernel = functools.partial(_flash_kernel, scale=scale, softcap=softcap, span=span)
    q_spec = pl.BlockSpec((rep, block_q, hd), lambda g, qi, ki: (g, qi, 0))
    kv_spec = pl.BlockSpec((None, block_k, hd), _kv_index_map(**span))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep * block_q, LANES), jnp.float32),
            pltpu.VMEM((rep * block_q, LANES), jnp.float32),
            pltpu.VMEM((rep * block_q, hd), jnp.float32),
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
