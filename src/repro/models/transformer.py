"""Model assembly for every assigned architecture family.

One ``init_params`` / ``forward_hidden`` / ``loss_fn`` / ``prefill_fn`` /
``decode_fn`` quintet covers all 10 archs through family-specific block
stacks, all scanned over layers (compact HLO, fast 512-device compiles)
with configurable remat:

* dense / vlm    — [attn + MLP] x L            (gemma2: [local, global] pairs)
* moe            — [attn + MoE] x L            (RailS dispatch inside MoE)
* hybrid(zamba2) — [6 x mamba + shared-attn] x 6 + trailing mamba
* ssm(xlstm)     — [mLSTM, sLSTM] x 6
* audio(whisper) — encoder [attn+MLP] x L  +  decoder [self+cross+MLP] x L

Caches are stacked along the scan dimension so decode is also a scan.
``shard_fn`` is an injection point for sharding constraints at block
boundaries (supplied by :mod:`repro.parallel.sharding`).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ModelConfig
from .attention import attn_decode, attn_forward, attn_init, init_kv_cache
from .layers import (
    chunked_cross_entropy,
    dtype_of,
    embedding_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    soft_cap,
)
from .mamba import init_mamba_cache, mamba_decode, mamba_forward, mamba_init
from .moe import EXPERT_WEIGHTS, EpInfo, experts_read, moe_apply, moe_init
from .xlstm import (
    init_mlstm_cache,
    init_slstm_cache,
    mlstm_forward,
    mlstm_init,
    slstm_forward,
    slstm_init,
)

__all__ = ["init_params", "loss_fn", "prefill_fn", "decode_fn", "init_cache"]

Identity: Callable = lambda x, kind=None: x


def _stacked(init_one, key, n, *args):
    return jax.vmap(lambda k: init_one(k, *args))(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    dt = dtype_of(cfg)
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embedding_init(keys[1], cfg.vocab_size, cfg.d_model, dt)

    fam = cfg.family
    d = cfg.d_model
    if fam in ("dense", "vlm"):
        if cfg.attn_pattern == "alt_local_global":
            half = cfg.num_layers // 2
            params["blocks"] = {
                kind: {
                    "attn": _stacked(lambda k: attn_init(k, cfg, dt), keys[2 + i], half),
                    "mlp": _stacked(lambda k: mlp_init(k, d, cfg.d_ff, dt), keys[4 + i], half),
                    "ln1": jnp.ones((half, d), dt),
                    "ln2": jnp.ones((half, d), dt),
                    "post1": jnp.ones((half, d), dt),
                    "post2": jnp.ones((half, d), dt),
                }
                for i, kind in enumerate(("local", "global"))
            }
        else:
            n = cfg.num_layers
            params["blocks"] = {
                "attn": _stacked(lambda k: attn_init(k, cfg, dt), keys[2], n),
                "mlp": _stacked(lambda k: mlp_init(k, d, cfg.d_ff, dt), keys[3], n),
                "ln1": jnp.ones((n, d), dt),
                "ln2": jnp.ones((n, d), dt),
            }
    elif fam == "moe":
        n = cfg.num_layers
        params["blocks"] = {
            "attn": _stacked(lambda k: attn_init(k, cfg, dt), keys[2], n),
            "moe": _stacked(lambda k: moe_init(k, cfg, dt), keys[3], n),
            "ln1": jnp.ones((n, d), dt),
            "ln2": jnp.ones((n, d), dt),
        }
    elif fam == "hybrid":
        period = cfg.shared_attn_period
        n_groups = cfg.num_layers // period
        n_tail = cfg.num_layers - n_groups * period
        params["blocks"] = {
            "mamba": _stacked(lambda k: mamba_init(k, cfg, dt), keys[2], n_groups * period),
            "mamba_ln": jnp.ones((n_groups * period, d), dt),
            "tail": _stacked(lambda k: mamba_init(k, cfg, dt), keys[3], max(n_tail, 1)),
            "tail_ln": jnp.ones((max(n_tail, 1), d), dt),
            "shared_attn": attn_init(keys[4], cfg, dt),
            "shared_mlp": mlp_init(keys[5], d, cfg.d_ff, dt),
            "shared_ln1": rmsnorm_init(d, dt),
            "shared_ln2": rmsnorm_init(d, dt),
        }
    elif fam == "ssm":
        n_m = sum(1 for c in cfg.xlstm_pattern if c == "m")
        n_s = sum(1 for c in cfg.xlstm_pattern if c == "s")
        params["blocks"] = {
            "m": _stacked(lambda k: mlstm_init(k, cfg, dt), keys[2], n_m),
            "m_ln": jnp.ones((n_m, d), dt),
            "s": _stacked(lambda k: slstm_init(k, cfg, dt), keys[3], n_s),
            "s_ln": jnp.ones((n_s, d), dt),
        }
    elif fam == "audio":
        ne, nd = cfg.encoder_layers, cfg.num_layers
        params["enc_pos"] = embedding_init(keys[6], cfg.encoder_seq, d, dt)
        params["enc_final_norm"] = rmsnorm_init(d, dt)
        params["blocks"] = {
            "enc": {
                "attn": _stacked(lambda k: attn_init(k, cfg, dt), keys[2], ne),
                "mlp": _stacked(lambda k: mlp_init(k, d, cfg.d_ff, dt), keys[3], ne),
                "ln1": jnp.ones((ne, d), dt),
                "ln2": jnp.ones((ne, d), dt),
            },
            "dec": {
                "self_attn": _stacked(lambda k: attn_init(k, cfg, dt), keys[4], nd),
                "cross_attn": _stacked(lambda k: attn_init(k, cfg, dt, cross=True), keys[5], nd),
                "mlp": _stacked(lambda k: mlp_init(k, d, cfg.d_ff, dt), keys[7], nd),
                "ln1": jnp.ones((nd, d), dt),
                "ln2": jnp.ones((nd, d), dt),
                "ln3": jnp.ones((nd, d), dt),
            },
        }
    else:
        raise ValueError(f"unknown family {fam!r}")
    return params


# ---------------------------------------------------------------------------
# Forward (full sequence): train / prefill
# ---------------------------------------------------------------------------


def _window_for(cfg: ModelConfig, kind: str) -> Optional[int]:
    if cfg.attn_pattern == "swa":
        return cfg.sliding_window
    if cfg.attn_pattern == "alt_local_global" and kind == "local":
        return cfg.sliding_window
    return None


def _maybe_remat(cfg: ModelConfig, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _dense_block(x, p, cfg: ModelConfig, positions, kind: str, shard_fn, collect_kv=False):
    h = attn_forward(
        p["attn"], cfg, rmsnorm(x, p["ln1"], cfg.rms_eps), positions,
        window=_window_for(cfg, kind), return_kv=collect_kv,
    )
    kv = None
    if collect_kv:
        h, kv = h
    if cfg.use_post_norm:
        h = rmsnorm(h, p["post1"], cfg.rms_eps)
    x = shard_fn(x + h, "resid")
    h2 = mlp_apply(p["mlp"], rmsnorm(x, p["ln2"], cfg.rms_eps), cfg.act)
    if cfg.use_post_norm:
        h2 = rmsnorm(h2, p["post2"], cfg.rms_eps)
    x = shard_fn(x + h2, "resid")
    return (x, kv) if collect_kv else x


def _moe_block(x, p, cfg, positions, ep_info, shard_fn, collect_kv=False):
    h = attn_forward(
        p["attn"], cfg, rmsnorm(x, p["ln1"], cfg.rms_eps), positions,
        window=_window_for(cfg, "swa"), return_kv=collect_kv,
    )
    kv = None
    if collect_kv:
        h, kv = h
    x = shard_fn(x + h, "resid")
    out, aux, counts = moe_apply(p["moe"], cfg, rmsnorm(x, p["ln2"], cfg.rms_eps), ep_info)
    x = shard_fn(x + out, "resid")
    return (x, aux, counts, kv) if collect_kv else (x, aux, counts)


def forward_hidden(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    ep_info: Optional[EpInfo] = None,
    shard_fn: Callable = Identity,
    collect_cache: bool = False,
):
    """Full-sequence forward. Returns ``(hidden, aux_metrics, caches|None)``."""
    dt = dtype_of(cfg)
    tokens = batch["tokens"]
    b, t = tokens.shape
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, dt)
    x = shard_fn(x, "resid")
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        if cfg.use_mrope:
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, t))
    aux = {"moe_aux": jnp.float32(0.0), "moe_counts": jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)}
    caches = {} if collect_cache else None
    fam = cfg.family
    bl = params["blocks"]

    if fam in ("dense", "vlm"):
        if cfg.attn_pattern == "alt_local_global":
            def pair(xc, p):
                xc = _dense_block(xc, p["local"], cfg, positions, "local", shard_fn)
                xc = _dense_block(xc, p["global"], cfg, positions, "global", shard_fn)
                return xc, None
            if collect_cache:
                def pair_kv(xc, p):
                    xc, kv_l = _dense_block(xc, p["local"], cfg, positions, "local", shard_fn, True)
                    xc, kv_g = _dense_block(xc, p["global"], cfg, positions, "global", shard_fn, True)
                    return xc, {"local": kv_l, "global": kv_g}
                x, kvs = jax.lax.scan(_maybe_remat(cfg, pair_kv), x, bl)
                caches["kv"] = kvs
            else:
                x, _ = jax.lax.scan(_maybe_remat(cfg, pair), x, bl)
        else:
            def body(xc, p):
                return _dense_block(xc, p, cfg, positions, "full", shard_fn), None
            if collect_cache:
                def body_kv(xc, p):
                    xc, kv = _dense_block(xc, p, cfg, positions, "full", shard_fn, True)
                    return xc, kv
                x, kvs = jax.lax.scan(_maybe_remat(cfg, body_kv), x, bl)
                caches["kv"] = kvs
            else:
                x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, bl)

    elif fam == "moe":
        if collect_cache:
            def body_kv(xc, p):
                xc, a, c, kv = _moe_block(xc, p, cfg, positions, ep_info, shard_fn, True)
                return xc, (a, c, kv)
            x, (auxs, counts, kvs) = jax.lax.scan(_maybe_remat(cfg, body_kv), x, bl)
            caches["kv"] = kvs
        else:
            def body(xc, p):
                xc, a, c = _moe_block(xc, p, cfg, positions, ep_info, shard_fn)
                return xc, (a, c)
            x, (auxs, counts) = jax.lax.scan(_maybe_remat(cfg, body), x, bl)
        aux["moe_aux"] = jnp.sum(auxs)
        aux["moe_counts"] = jnp.sum(counts, axis=0)

    elif fam == "hybrid":
        period = cfg.shared_attn_period
        n_groups = cfg.num_layers // period
        n_tail = cfg.num_layers - n_groups * period
        mamba_p = jax.tree.map(
            lambda a: a.reshape(n_groups, period, *a.shape[1:]), bl["mamba"]
        )
        mamba_ln = bl["mamba_ln"].reshape(n_groups, period, -1)
        shared = {k: bl[k] for k in ("shared_attn", "shared_mlp", "shared_ln1", "shared_ln2")}
        states: list = []

        def group(xc, p):
            pm, ln = p
            def inner(xc2, pi):
                pm_i, ln_i = pi
                out, state = mamba_forward(pm_i, cfg, rmsnorm(xc2, ln_i, cfg.rms_eps))
                return shard_fn(xc2 + out, "resid"), state
            xc, st = jax.lax.scan(inner, xc, (pm, ln))
            h = attn_forward(shared["shared_attn"], cfg,
                             rmsnorm(xc, shared["shared_ln1"], cfg.rms_eps), positions)
            xc = shard_fn(xc + h, "resid")
            h2 = mlp_apply(shared["shared_mlp"], rmsnorm(xc, shared["shared_ln2"], cfg.rms_eps), cfg.act)
            xc = shard_fn(xc + h2, "resid")
            return xc, st
        x, _states = jax.lax.scan(_maybe_remat(cfg, group), x, (mamba_p, mamba_ln))
        if n_tail:
            tail_p = jax.tree.map(lambda a: a[:n_tail], bl["tail"])
            def tail(xc, pi):
                pm_i, ln_i = pi
                out, state = mamba_forward(pm_i, cfg, rmsnorm(xc, ln_i, cfg.rms_eps))
                return shard_fn(xc + out, "resid"), state
            x, _ = jax.lax.scan(_maybe_remat(cfg, tail), x, (tail_p, bl["tail_ln"][:n_tail]))

    elif fam == "ssm":
        def super_block(xc, p):
            pm, ln_m, ps, ln_s = p
            out, _ = mlstm_forward(pm, cfg, rmsnorm(xc, ln_m, cfg.rms_eps))
            xc = shard_fn(xc + out, "resid")
            out, _ = slstm_forward(ps, cfg, rmsnorm(xc, ln_s, cfg.rms_eps))
            return shard_fn(xc + out, "resid"), None
        x, _ = jax.lax.scan(
            _maybe_remat(cfg, super_block), x, (bl["m"], bl["m_ln"], bl["s"], bl["s_ln"])
        )

    elif fam == "audio":
        memory = _whisper_encode(params, cfg, batch, shard_fn)
        def dec_body(xc, p):
            h = attn_forward(p["self_attn"], cfg, rmsnorm(xc, p["ln1"], cfg.rms_eps),
                             positions, return_kv=collect_cache)
            kv = None
            if collect_cache:
                h, kv = h
            xc = shard_fn(xc + h, "resid")
            h = attn_forward(p["cross_attn"], cfg, rmsnorm(xc, p["ln2"], cfg.rms_eps),
                             None, kv_override=memory, return_kv=collect_cache)
            ckv = None
            if collect_cache:
                h, ckv = h
            xc = shard_fn(xc + h, "resid")
            h = mlp_apply(p["mlp"], rmsnorm(xc, p["ln3"], cfg.rms_eps), cfg.act)
            xc = shard_fn(xc + h, "resid")
            return xc, (kv, ckv) if collect_cache else None
        if collect_cache:
            x, (kvs, ckvs) = jax.lax.scan(_maybe_remat(cfg, dec_body), x, bl["dec"])
            caches["kv"] = kvs
            caches["cross_kv"] = ckvs
        else:
            x, _ = jax.lax.scan(_maybe_remat(cfg, dec_body), x, bl["dec"])
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return x, aux, caches


def _whisper_encode(params, cfg: ModelConfig, batch, shard_fn):
    """Frontend stub: ``batch['embeds']`` are precomputed frame embeddings."""
    mem = batch["embeds"].astype(dtype_of(cfg))
    mem = mem + params["enc_pos"][None, : mem.shape[1]]
    def body(xc, p):
        h = attn_forward(p["attn"], cfg, rmsnorm(xc, p["ln1"], cfg.rms_eps), None, causal=False)
        xc = shard_fn(xc + h, "resid")
        h = mlp_apply(p["mlp"], rmsnorm(xc, p["ln2"], cfg.rms_eps), cfg.act)
        return shard_fn(xc + h, "resid"), None
    mem, _ = jax.lax.scan(_maybe_remat(cfg, body), mem, params["blocks"]["enc"])
    return rmsnorm(mem, params["enc_final_norm"], cfg.rms_eps)


# ---------------------------------------------------------------------------
# Heads: loss / prefill / decode
# ---------------------------------------------------------------------------


def _vocab_matrix(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"].T


def loss_fn(params, cfg: ModelConfig, batch, ep_info=None, shard_fn: Callable = Identity):
    hidden, aux, _ = forward_hidden(params, cfg, batch, ep_info, shard_fn)
    with jax.named_scope(obs.HEAD):
        nll = chunked_cross_entropy(
            hidden, _vocab_matrix(params, cfg), batch["labels"],
            chunk=cfg.xent_chunk, final_softcap=cfg.final_logit_softcap,
            shard_fn=None if shard_fn is Identity else shard_fn,
        )
    loss = nll + cfg.router_aux_coef * aux["moe_aux"]
    metrics = {"nll": nll, "moe_aux": aux["moe_aux"], "moe_counts": aux["moe_counts"]}
    return loss, metrics


@obs.scoped(obs.HEAD)
def logits_last(params, cfg: ModelConfig, hidden):
    h_last = hidden[:, -1]
    logits = jnp.einsum("bd,dv->bv", h_last, _vocab_matrix(params, cfg)).astype(jnp.float32)
    return soft_cap(logits, cfg.final_logit_softcap)


def prefill_fn(params, cfg: ModelConfig, batch, ep_info=None, shard_fn: Callable = Identity):
    """Full-sequence prefill: last-position logits + caches (KV to length T)."""
    hidden, aux, caches = forward_hidden(
        params, cfg, batch, ep_info, shard_fn, collect_cache=cfg.family in ("dense", "vlm", "moe", "audio")
    )
    return logits_last(params, cfg, hidden), caches, aux


# -- decode ------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Stacked decode caches matching the scan layout of ``decode_fn``."""
    dt = dtype_of(cfg)
    fam = cfg.family

    def kv(n):
        return jax.vmap(lambda _: init_kv_cache(cfg, batch, max_len, dt))(jnp.arange(n))

    if fam in ("dense", "vlm"):
        if cfg.attn_pattern == "alt_local_global":
            half = cfg.num_layers // 2
            return {"local": kv(half), "global": kv(half)}
        return {"kv": kv(cfg.num_layers)}
    if fam == "moe":
        return {"kv": kv(cfg.num_layers)}
    if fam == "hybrid":
        period = cfg.shared_attn_period
        n_groups = cfg.num_layers // period
        n_tail = cfg.num_layers - n_groups * period
        return {
            "mamba": jax.vmap(lambda _: init_mamba_cache(cfg, batch, dt))(
                jnp.arange(n_groups * period)
            ),
            "tail": jax.vmap(lambda _: init_mamba_cache(cfg, batch, dt))(
                jnp.arange(max(n_tail, 1))
            ),
            "shared_kv": kv(n_groups),
        }
    if fam == "ssm":
        n_m = sum(1 for c in cfg.xlstm_pattern if c == "m")
        n_s = sum(1 for c in cfg.xlstm_pattern if c == "s")
        return {
            "m": jax.vmap(lambda _: jax.tree.map(jnp.asarray, init_mlstm_cache(cfg, batch)))(jnp.arange(n_m)),
            "s": jax.vmap(lambda _: jax.tree.map(jnp.asarray, init_slstm_cache(cfg, batch)))(jnp.arange(n_s)),
        }
    if fam == "audio":
        enc = cfg.encoder_seq
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        return {
            "kv": kv(cfg.num_layers),
            "cross_kv": {
                "k": jnp.zeros((cfg.num_layers, batch, enc, hkv, hd), dt),
                "v": jnp.zeros((cfg.num_layers, batch, enc, hkv, hd), dt),
            },
        }
    raise ValueError(fam)


def _scan_layers_inplace(body, params_stack, cache, x, n_layers: int):
    """Decode-layer scan with the cache in the CARRY (not xs/ys).

    Carrying the full stacked cache and updating layer ``i`` via
    dynamic-update-slice lets XLA keep ONE cache buffer alive (in-place
    while-loop update); the xs->ys form double-buffers the entire cache,
    which at 32k-context scale is gigabytes per device.
    """
    def step(carry, inputs):
        xc, cache_c = carry
        i, p = inputs
        c_l = jax.tree.map(lambda a: a[i], cache_c)
        xc, c_new = body(xc, p, c_l)
        cache_c = jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u.astype(a.dtype), i, 0),
            cache_c,
            c_new,
        )
        return (xc, cache_c), None

    (x, cache), _ = jax.lax.scan(
        step, (x, cache), (jnp.arange(n_layers), params_stack)
    )
    return x, cache


def decode_fn(params, cfg: ModelConfig, cache: dict, tokens, pos, ep_info=None,
              shard_fn: Callable = Identity, return_counts: bool = False):
    """One decode step. ``tokens: (B, 1)``, ``pos``: scalar position.

    Returns ``(logits (B, V-softcapped), new_cache)``, or with
    ``return_counts=True`` ``(logits, new_cache, moe_counts, experts_read)``
    where ``moe_counts`` is the step's per-expert routed-token counts summed
    over layers (``(num_experts,)`` int32; all zeros for non-MoE
    families) — the real gating trace the serving-path fabric replay
    (``launch/serve.py --sim-fabric``) consumes — and ``experts_read``
    (int32) the (layer, expert) weight sets the step read
    (``moe.experts_read``), summed over layers.
    """
    dt = dtype_of(cfg)
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, dt)
    fam = cfg.family
    bl = params["blocks"]
    new_cache: dict = {}
    moe_counts = jnp.zeros((max(cfg.num_experts, 1),), jnp.int32)
    read = jnp.int32(0)

    if fam in ("dense", "vlm", "moe"):
        is_moe = fam == "moe"
        if return_counts and is_moe and cfg.attn_pattern == "alt_local_global":
            # The alt-pattern branch has no MoE layers to count; failing
            # loudly beats replaying an all-zero gating trace.
            raise ValueError(
                "return_counts is not supported for MoE configs with "
                "attn_pattern='alt_local_global'"
            )
        if cfg.attn_pattern == "alt_local_global":
            def pair(xc, p, c):
                c_l, c_g = c["local"], c["global"]
                h, c_l = attn_decode(p["local"]["attn"], cfg,
                                     rmsnorm(xc, p["local"]["ln1"], cfg.rms_eps), c_l, pos,
                                     window=cfg.sliding_window)
                if cfg.use_post_norm:
                    h = rmsnorm(h, p["local"]["post1"], cfg.rms_eps)
                xc = xc + h
                h2 = mlp_apply(p["local"]["mlp"], rmsnorm(xc, p["local"]["ln2"], cfg.rms_eps), cfg.act)
                if cfg.use_post_norm:
                    h2 = rmsnorm(h2, p["local"]["post2"], cfg.rms_eps)
                xc = xc + h2
                h, c_g = attn_decode(p["global"]["attn"], cfg,
                                     rmsnorm(xc, p["global"]["ln1"], cfg.rms_eps), c_g, pos)
                if cfg.use_post_norm:
                    h = rmsnorm(h, p["global"]["post1"], cfg.rms_eps)
                xc = xc + h
                h2 = mlp_apply(p["global"]["mlp"], rmsnorm(xc, p["global"]["ln2"], cfg.rms_eps), cfg.act)
                if cfg.use_post_norm:
                    h2 = rmsnorm(h2, p["global"]["post2"], cfg.rms_eps)
                return xc + h2, {"local": c_l, "global": c_g}
            x, new_cache = _scan_layers_inplace(
                pair, bl, {"local": cache["local"], "global": cache["global"]},
                x, cfg.num_layers // 2,
            )
        elif is_moe:
            # The expert weights stay stacked over layers, out of the scan's
            # per-layer slices: the decode MoE kernel reads a layer's experts
            # from the stack by index, where a slice that fed it would be
            # copied out first. With ``return_counts`` the carry also sums
            # per-expert counts and expert weight sets read over layers: the
            # step's gating trace, as forward_hidden reports for training.
            ep = ep_info.ep if ep_info is not None else 1
            experts = {k: bl["moe"][k] for k in EXPERT_WEIGHTS}
            per_layer = {**bl, "moe": {k: v for k, v in bl["moe"].items() if k not in experts}}

            def body(carry, layer_p, c):
                xc, cnts, rd = carry
                i, p = layer_p
                h, c = attn_decode(p["attn"], cfg, rmsnorm(xc, p["ln1"], cfg.rms_eps),
                                   c, pos, window=_window_for(cfg, "swa"))
                xc = xc + h
                out, _a, cnt = moe_apply({**p["moe"], **experts}, cfg,
                                         rmsnorm(xc, p["ln2"], cfg.rms_eps), ep_info, layer=i)
                if return_counts:
                    cnts, rd = cnts + cnt, rd + experts_read(cfg, cnt, tokens.size, ep)
                return (xc + out, cnts, rd), c
            (x, moe_counts, read), kv = _scan_layers_inplace(
                body, (jnp.arange(cfg.num_layers), per_layer), cache["kv"],
                (x, moe_counts, read), cfg.num_layers,
            )
            new_cache = {"kv": kv}
        else:
            def body(xc, p, c):
                h, c = attn_decode(p["attn"], cfg, rmsnorm(xc, p["ln1"], cfg.rms_eps),
                                   c, pos, window=_window_for(cfg, "swa"))
                xc = xc + h
                out = mlp_apply(p["mlp"], rmsnorm(xc, p["ln2"], cfg.rms_eps), cfg.act)
                return xc + out, c
            x, kv = _scan_layers_inplace(body, bl, cache["kv"], x, cfg.num_layers)
            new_cache = {"kv": kv}

    elif fam == "hybrid":
        period = cfg.shared_attn_period
        n_groups = cfg.num_layers // period
        n_tail = cfg.num_layers - n_groups * period
        mamba_p = jax.tree.map(lambda a: a.reshape(n_groups, period, *a.shape[1:]), bl["mamba"])
        mamba_ln = bl["mamba_ln"].reshape(n_groups, period, -1)
        mcache = jax.tree.map(lambda a: a.reshape(n_groups, period, *a.shape[1:]), cache["mamba"])
        def group(xc, xs):
            pm, ln, mc, kc = xs
            def inner(xc2, ys):
                pm_i, ln_i, mc_i = ys
                out, mc_i = mamba_decode(pm_i, cfg, rmsnorm(xc2, ln_i, cfg.rms_eps), mc_i)
                return xc2 + out, mc_i
            xc, mc = jax.lax.scan(inner, xc, (pm, ln, mc))
            h, kc = attn_decode(bl["shared_attn"], cfg,
                                rmsnorm(xc, bl["shared_ln1"], cfg.rms_eps), kc, pos)
            xc = xc + h
            h2 = mlp_apply(bl["shared_mlp"], rmsnorm(xc, bl["shared_ln2"], cfg.rms_eps), cfg.act)
            return xc + h2, (mc, kc)
        x, (mc, kc) = jax.lax.scan(group, x, (mamba_p, mamba_ln, mcache, cache["shared_kv"]))
        new_cache["mamba"] = jax.tree.map(lambda a: a.reshape(n_groups * period, *a.shape[2:]), mc)
        new_cache["shared_kv"] = kc
        if n_tail:
            def tail(xc, ys):
                pm_i, ln_i, mc_i = ys
                out, mc_i = mamba_decode(pm_i, cfg, rmsnorm(xc, ln_i, cfg.rms_eps), mc_i)
                return xc + out, mc_i
            tail_p = jax.tree.map(lambda a: a[:n_tail], bl["tail"])
            tail_c = jax.tree.map(lambda a: a[:n_tail], cache["tail"])
            x, tc = jax.lax.scan(tail, x, (tail_p, bl["tail_ln"][:n_tail], tail_c))
            pad = jax.tree.map(lambda a: a[n_tail:], cache["tail"])
            new_cache["tail"] = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), tc, pad)
        else:
            new_cache["tail"] = cache["tail"]

    elif fam == "ssm":
        def super_block(xc, xs):
            pm, ln_m, ps, ln_s, cm, cs = xs
            out, cm = mlstm_forward(pm, cfg, rmsnorm(xc, ln_m, cfg.rms_eps), cache=cm)
            xc = xc + out
            out, cs = slstm_forward(ps, cfg, rmsnorm(xc, ln_s, cfg.rms_eps), cache=cs)
            return xc + out, (cm, cs)
        x, (cm, cs) = jax.lax.scan(
            super_block, x, (bl["m"], bl["m_ln"], bl["s"], bl["s_ln"], cache["m"], cache["s"])
        )
        new_cache = {"m": cm, "s": cs}

    elif fam == "audio":
        # cross-attn memory is static per layer; self-attn kv carried inplace.
        def dec_step(xc, p, c):
            c_self, cc = c["kv"], c["cross"]
            h, c_self = attn_decode(p["self_attn"], cfg,
                                    rmsnorm(xc, p["ln1"], cfg.rms_eps), c_self, pos)
            xc = xc + h
            h, _ = attn_decode(p["cross_attn"], cfg, rmsnorm(xc, p["ln2"], cfg.rms_eps),
                               c_self, pos, kv_override_cache=cc)
            xc = xc + h
            h = mlp_apply(p["mlp"], rmsnorm(xc, p["ln3"], cfg.rms_eps), cfg.act)
            return xc + h, {"kv": c_self, "cross": cc}
        x, merged = _scan_layers_inplace(
            dec_step, bl["dec"], {"kv": cache["kv"], "cross": cache["cross_kv"]},
            x, cfg.num_layers,
        )
        new_cache = {"kv": merged["kv"], "cross_kv": merged["cross"]}
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = logits_last(params, cfg, x)
    if return_counts:
        return logits, new_cache, moe_counts, read
    return logits, new_cache
