"""Mixture-of-Experts layer with RailS-scheduled expert-parallel dispatch.

Layout strategy (DESIGN.md §4.2):

* Tokens are flattened ``(B, T, D) -> (Ntot, D)`` and factored
  ``(ep, G, Tg, D)``: ``ep`` = expert-parallel shards (manual axis inside a
  partial ``shard_map``), ``G`` = dispatch groups (auto-sharded over the
  data axis), ``Tg`` = tokens per group (capacity is per group, so all
  scatter/cumsum work stays group-local and partitions cleanly).
* Dispatch: per group, top-k routing -> capacity-bounded buckets
  ``(E, C, D)`` -> all-to-all over the ``expert`` axis. The all-to-all is
  the paper's target collective: ``cfg.dispatch_mode`` selects
  ``dense`` (one monolithic collective), ``ring``, ``rails`` (LPT-scheduled
  N-rail spraying — the paper), or ``spray`` (Theorem-3 1/N feature spray).
* Expert FFN: grouped GEMM over local experts (Pallas kernel on TPU when
  running in a fully-manual region; einsum under auto partitioning).
* Combine: inverse all-to-all, per-group gather, weighted sum over k.

Decode-sized batches (a handful of tokens) use a dense-EP path instead:
every expert shard computes its local experts for all tokens and the
results sum across the expert axis — no dispatch, no capacity drops.

The gating count vector (the paper's "known traffic matrix" ``D``) is
returned to the caller for the host-side LPT planner.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.rails_all_to_all import build_rail_schedule, rails_all_to_all, ring_all_to_all, spray_all_to_all, dense_all_to_all
from .layers import dense_init

__all__ = ["moe_init", "moe_apply", "EpInfo"]


class EpInfo:
    """Expert-parallel context: mesh + axis names for the partial shard_map."""

    def __init__(self, mesh, expert_axis: str, ep: int, data_axis: str = "data"):
        self.mesh = mesh
        self.expert_axis = expert_axis
        self.ep = ep
        self.data_axis = data_axis


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    scale_in, scale_out = d**-0.5, f**-0.5

    def expert_w(k, d_in, d_out, scale):
        return (
            jax.random.normal(k, (e, d_in, d_out), dtype=jnp.float32) * scale
        ).astype(dtype)

    return {
        "router": dense_init(ks[0], d, e, jnp.float32),  # router math in fp32
        "w_gate": expert_w(ks[1], d, f, scale_in),
        "w_up": expert_w(ks[2], d, f, scale_in),
        "w_down": expert_w(ks[3], f, d, scale_out),
    }


def _gate(x2: jnp.ndarray, router: jnp.ndarray, cfg: ModelConfig):
    """Top-k routing. ``x2: (..., D)`` -> idx/weights ``(..., k)``, aux, counts."""
    logits = jnp.einsum("...d,de->...e", x2.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    e = cfg.num_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (..., k, E)
    frac = jnp.mean(jnp.sum(onehot, axis=-2), axis=tuple(range(onehot.ndim - 2)))
    prob_mean = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    aux = e * jnp.sum(frac * prob_mean) / cfg.experts_per_token
    counts = jnp.sum(onehot, axis=tuple(range(onehot.ndim - 1))).astype(jnp.int32)
    return idx, weights.astype(x2.dtype), aux, counts


def _dispatch_group(x_g, idx_g, w_g, num_experts: int, cap: int):
    """One group's capacity dispatch. ``x_g: (Tg, D)``, ``idx_g/w_g: (Tg, k)``.

    Returns buckets ``(E, C, D)`` plus (flat_e, slot, keep, w_flat) for the
    combine gather.
    """
    tg, k = idx_g.shape
    d = x_g.shape[-1]
    flat_e = idx_g.reshape(-1)  # (Tg*k,)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.sum(pos * onehot, axis=-1)  # (Tg*k,) position within expert
    keep = pos < cap
    slot = jnp.minimum(pos, cap - 1)
    x_rep = jnp.repeat(x_g, k, axis=0)  # (Tg*k, D)
    contrib = x_rep * keep[:, None].astype(x_g.dtype)
    buckets = jnp.zeros((num_experts, cap, d), dtype=x_g.dtype)
    buckets = buckets.at[flat_e, slot].add(contrib)
    return buckets, (flat_e, slot, keep, w_g.reshape(-1))


def _combine_group(buckets_out, meta, tg: int, k: int):
    flat_e, slot, keep, w_flat = meta
    vals = buckets_out[flat_e, slot]  # (Tg*k, D)
    vals = vals * (keep.astype(vals.dtype) * w_flat)[:, None]
    return vals.reshape(tg, k, -1).sum(axis=1)


def _expert_ffn(xe: jnp.ndarray, params: dict, cfg: ModelConfig, local_slice=None):
    """Grouped FFN. ``xe: (E_loc, M, D)`` -> ``(E_loc, M, D)``.

    ``local_slice`` selects this shard's experts from the stacked weights
    (inside shard_map the weights arrive already sliced — pass None).
    """
    wg, wu, wd = params["w_gate"], params["w_up"], params["w_down"]
    if local_slice is not None:
        wg, wu, wd = wg[local_slice], wu[local_slice], wd[local_slice]
    gate = jnp.einsum("gnd,gdf->gnf", xe, wg)
    up = jnp.einsum("gnd,gdf->gnf", xe, wu)
    act = jax.nn.silu(gate) if cfg.act == "silu" else jax.nn.gelu(gate)
    return jnp.einsum("gnf,gfd->gnd", act * up, wd)


def _a2a(payload: jnp.ndarray, axis: Optional[str], cfg: ModelConfig):
    """The paper's collective. ``payload: (ep, G, ...)``, dim0 = peer."""
    if axis is None or payload.shape[0] == 1:
        return payload
    mode = cfg.dispatch_mode
    if mode == "dense":
        return dense_all_to_all(payload, axis)
    if mode == "ring":
        return ring_all_to_all(payload, axis)
    if mode == "spray":
        return spray_all_to_all(payload, axis, cfg.num_rails)
    if mode == "rails":
        chunks = max(1, min(cfg.dispatch_chunks, payload.shape[1]))
        sched = build_rail_schedule(payload.shape[0], cfg.num_rails, chunks)
        return rails_all_to_all(payload, axis, sched)
    raise ValueError(f"unknown dispatch_mode {cfg.dispatch_mode!r}")


def _moe_body(x_sh, params, cfg: ModelConfig, ep: int, axis: Optional[str]):
    """Per-expert-shard MoE. ``x_sh: (1|ep_local, G, Tg, D)`` (dim0 manual)."""
    e = cfg.num_experts
    e_loc = e // ep
    x_loc = x_sh[0]  # (G, Tg, D) — shard-local view
    g, tg, d = x_loc.shape
    cap = max(1, int(tg * cfg.experts_per_token * cfg.capacity_factor / e))

    idx, w, aux, counts = _gate(x_loc, params["router"], cfg)
    buckets, meta = jax.vmap(
        functools.partial(_dispatch_group, num_experts=e, cap=cap)
    )(x_loc, idx, w)  # (G, E, C, D)

    payload = buckets.reshape(g, ep, e_loc, cap, d).transpose(1, 0, 2, 3, 4)
    payload = _a2a(payload, axis, cfg)  # (ep, G, E_loc, C, D) dim0 = source
    xe = payload.transpose(2, 0, 1, 3, 4).reshape(e_loc, ep * g * cap, d)

    # Inside shard_map the expert weights arrive pre-sliced to E_loc.
    local = {k: params[k] for k in ("w_gate", "w_up", "w_down")}
    ye = _expert_ffn(xe, local, cfg)

    back = ye.reshape(e_loc, ep, g, cap, d).transpose(1, 2, 0, 3, 4)
    back = _a2a(back, axis, cfg)  # (ep, G, E_loc, C, D) dim0 = dest-expert shard
    buckets_out = back.transpose(1, 0, 2, 3, 4).reshape(g, e, cap, d)

    out = jax.vmap(functools.partial(_combine_group, tg=tg, k=cfg.experts_per_token))(
        buckets_out, meta
    )
    return out[None], aux[None], counts[None]  # restore manual dim


def _moe_dense_small(x2, params, cfg: ModelConfig):
    """Dense-EP path for decode-sized token counts: all experts computed for
    all tokens (weights sharded over the expert axis; XLA reduces)."""
    idx, w, aux, counts = _gate(x2, params["router"], cfg)
    e = cfg.num_experts
    gates = jnp.zeros((x2.shape[0], e), dtype=x2.dtype)
    gates = jax.vmap(lambda g_row, i_row, w_row: g_row.at[i_row].add(w_row))(
        gates, idx, w
    )
    gate_h = jnp.einsum("nd,edf->nef", x2, params["w_gate"])
    up_h = jnp.einsum("nd,edf->nef", x2, params["w_up"])
    act = jax.nn.silu(gate_h) if cfg.act == "silu" else jax.nn.gelu(gate_h)
    ye = jnp.einsum("nef,efd->ned", act * up_h, params["w_down"])
    out = jnp.einsum("ned,ne->nd", ye, gates)
    return out, aux, counts


def moe_apply(
    params: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    ep_info: Optional[EpInfo] = None,
    group_tokens: int = 1024,
):
    """MoE layer. ``x: (B, T, D)`` -> ``(out, aux_loss, gating_counts)``."""
    b, t, d = x.shape
    n = b * t
    ep = ep_info.ep if ep_info is not None else 1
    x2 = x.reshape(n, d)

    # Decode-sized batches: dense-EP, no dispatch (and no capacity drops).
    if n < ep * 8 or n % ep != 0:
        out, aux, counts = _moe_dense_small(x2, params, cfg)
        return out.reshape(b, t, d), aux, counts

    rows = n // ep
    tg = min(group_tokens, rows)
    while rows % tg:
        tg -= 1
    g = rows // tg
    x4 = x2.reshape(ep, g, tg, d)

    if ep_info is None or ep == 1:
        out, aux, counts = _moe_body(x4, params, cfg, 1, None)
        out = out.reshape(n, d)
        return out.reshape(b, t, d), aux[0], counts[0]

    axis = ep_info.expert_axis
    body = functools.partial(_moe_body, cfg=cfg, ep=ep, axis=axis)
    pspec = {
        "router": P(),
        "w_gate": P(axis, None, None),
        "w_up": P(axis, None, None),
        "w_down": P(axis, None, None),
    }
    out, aux, counts = jax.shard_map(
        lambda xs, pr: body(xs, pr),
        mesh=ep_info.mesh,
        in_specs=(P(axis, None, None, None), pspec),
        out_specs=(P(axis, None, None, None), P(axis), P(axis, None)),
        axis_names={axis},
    )(x4, params)
    out = out.reshape(n, d)
    # aux/counts are per-shard; average/sum across shards happens in fp32
    # outside (they are tiny).
    return out.reshape(b, t, d), jnp.mean(aux), jnp.sum(counts, axis=0)
