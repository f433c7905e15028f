"""Step-function factories: train / prefill / decode, mesh-aware.

``make_train_step`` builds a jit-able ``(params, opt_state, batch) ->
(params, opt_state, metrics)`` with microbatched gradient accumulation
(fp32 accumulator, scanned), remat'd model blocks, and AdamW. Sharding
enters through the ctx-derived ``shard_fn`` + in/out shardings at the jit
boundary (see launch/dryrun.py and launch/train.py).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeSpec
from ..models import decode_fn, init_params, loss_fn, prefill_fn
from ..optim import AdamWConfig, adamw_init, adamw_update
from ..parallel.mesh_view import MeshContext
from ..parallel.sharding import cache_pspecs, make_shard_fn, param_pspecs, to_shardings

__all__ = [
    "make_train_step",
    "make_prefill_step",
    "make_decode_step",
    "abstract_train_state",
]


def _split_microbatches(batch: dict, n_mb: int) -> dict:
    def split(x):
        b = x.shape[0]
        return x.reshape(n_mb, b // n_mb, *x.shape[1:])

    return {k: split(v) for k, v in batch.items()}


def make_train_step(
    cfg: ModelConfig,
    ctx: MeshContext,
    shape: ShapeSpec,
    opt_cfg: Optional[AdamWConfig] = None,
):
    opt_cfg = opt_cfg or AdamWConfig()
    shard_fn = make_shard_fn(ctx)
    ep_info = ctx.ep_info
    n_mb = shape.num_microbatches

    def mb_loss(params, mb):
        return loss_fn(params, cfg, mb, ep_info, shard_fn)

    # Hillclimb lever (EXPERIMENTS.md §Perf): constrain the fp32 gradient
    # accumulator to the parameter shardings so per-microbatch gradient
    # reduction lowers to reduce-scatter into sharded buffers instead of
    # all-reduce into replicated ones.
    shard_grad_acc = os.environ.get("REPRO_SHARD_GRAD_ACC", "0") == "1"
    grad_shardings = None

    def train_step(params, opt_state, batch):
        batch_mb = _split_microbatches(batch, n_mb)
        g_constrain = (
            (lambda t: jax.tree.map(jax.lax.with_sharding_constraint, t,
                                    to_shardings(ctx, param_pspecs(cfg, ctx, params))))
            if shard_grad_acc
            else (lambda t: t)
        )

        def body(carry, mb):
            g_acc, loss_acc = carry
            (loss, metrics), grads = jax.value_and_grad(mb_loss, has_aux=True)(
                params, mb
            )
            g_acc = g_constrain(jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_mb, g_acc, grads
            ))
            return (g_acc, loss_acc + loss / n_mb), metrics

        g0 = g_constrain(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        )
        # Hillclimb lever (EXPERIMENTS.md §Perf): the FSDP weight gathers are
        # loop-invariant but XLA cannot hoist them out of a while body —
        # unrolling the microbatch loop lets CSE share one gather across all
        # microbatches (HLO grows n_mb-fold; collective bytes drop ~n_mb-fold).
        if os.environ.get("REPRO_UNROLL_MB", "0") == "1":
            carry = (g0, jnp.float32(0.0))
            metrics_list = []
            for i in range(n_mb):
                mb = jax.tree.map(lambda a: a[i], batch_mb)
                carry, m = body(carry, mb)
                metrics_list.append(m)
            grads, loss = carry
            metrics = jax.tree.map(lambda *ms: jnp.stack(ms), *metrics_list)
        else:
            (grads, loss), metrics = jax.lax.scan(body, (g0, jnp.float32(0.0)), batch_mb)
        new_params, new_opt, stats = adamw_update(grads, opt_state, params, opt_cfg)
        out_metrics = {
            "loss": loss,
            "nll": jnp.mean(metrics["nll"]),
            "moe_aux": jnp.mean(metrics["moe_aux"]),
            "moe_counts": jnp.sum(metrics["moe_counts"], axis=0),
            **stats,
        }
        return new_params, new_opt, out_metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, ctx: MeshContext, shape: Optional[ShapeSpec] = None):
    shard_fn = make_shard_fn(ctx)
    ep_info = ctx.ep_info
    n_mb = shape.num_microbatches if shape is not None else 1

    def prefill_one(params, batch):
        logits, caches, _aux = prefill_fn(params, cfg, batch, ep_info, shard_fn)
        return logits, caches

    if n_mb == 1:
        return prefill_one

    def prefill_step(params, batch):
        """Batch-chunked prefill: full-sequence transients scale with the
        chunk, not the global request batch (MoE dispatch buffers at 32k
        sequence x 32 batch otherwise dominate the HBM budget)."""
        batch_mb = _split_microbatches(batch, n_mb)

        def body(_, mb):
            return None, prefill_one(params, mb)

        _, (logits, caches) = jax.lax.scan(body, None, batch_mb)
        logits = logits.reshape(-1, logits.shape[-1])
        if caches is not None:
            # (MB, L, Bc, ...) -> (L, MB*Bc, ...); constrain the target
            # layout explicitly or the transpose replicates multi-GiB caches.
            caches = jax.tree.map(
                lambda a: jnp.moveaxis(a, 0, 1).reshape(
                    a.shape[1], a.shape[0] * a.shape[2], *a.shape[3:]
                ),
                caches,
            )
            shardings = to_shardings(ctx, cache_pspecs(cfg, ctx, caches))
            caches = jax.tree.map(jax.lax.with_sharding_constraint, caches, shardings)
        return logits, caches

    return prefill_step


def make_decode_step(cfg: ModelConfig, ctx: MeshContext, return_counts: bool = False):
    """Decode-step factory. ``return_counts=True`` surfaces the step's
    per-expert routed-token counts and expert weight sets read
    (``(logits, cache, counts, experts_read)``) — the gating trace
    `launch/serve.py --sim-fabric` replays onto the simulated rail fabric."""
    shard_fn = make_shard_fn(ctx)
    ep_info = ctx.ep_info

    if return_counts:
        def decode_step_counts(params, cache, batch, pos):
            return decode_fn(
                params, cfg, cache, batch["tokens"], pos, ep_info, shard_fn,
                return_counts=True,
            )
        return decode_step_counts

    def decode_step(params, cache, batch, pos):
        logits, new_cache = decode_fn(
            params, cfg, cache, batch["tokens"], pos, ep_info, shard_fn
        )
        return logits, new_cache

    return decode_step


def abstract_train_state(cfg: ModelConfig):
    """(params, opt_state) as ShapeDtypeStructs via eval_shape — no alloc."""
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: adamw_init(params))
    return params, opt
