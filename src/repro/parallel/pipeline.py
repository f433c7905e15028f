"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

SPMD formulation (no per-stage programs): every device runs the same
schedule of ``n_mb + n_stages - 1`` ticks. Each tick, a device applies its
local stage block to its current activation and passes the result to the
next stage with a ``collective_permute``; stage 0 injects a fresh
microbatch, the last stage emits a finished one. Bubbles are the standard
GPipe ``(S-1)/(S-1+M)`` fraction.

Available as a config option and exercised by tests; the headline dry-runs
use DP x TP/EP (DESIGN.md §4.2).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe", "pipeline_loss"]


def gpipe(
    stage_fn: Callable,
    params_stacked,
    x_microbatches: jnp.ndarray,
    mesh,
    stage_axis: str = "stage",
):
    """Run ``stage_fn(params_stage, x) -> x`` as a pipeline.

    Args:
      stage_fn: one pipeline stage (a block of layers).
      params_stacked: pytree with leading dim ``n_stages`` (stage-sharded).
      x_microbatches: ``(n_mb, mb, ...)`` inputs.
      mesh: mesh containing ``stage_axis``.

    Returns ``(n_mb, mb, ...)`` outputs, equal to applying all stages
    sequentially to each microbatch.
    """
    n_stages = mesh.shape[stage_axis]
    n_mb = x_microbatches.shape[0]
    ticks = n_mb + n_stages - 1

    def per_shard(params_local, xs):
        # params_local: (1, ...) slice of this shard's stage params.
        p_mine = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(stage_axis)
        mb_shape = xs.shape[1:]
        # carries become device-varying over the stage axis inside the loop;
        # mark the (replicated) initial values accordingly.
        carry = jax.lax.pcast(
            jnp.zeros(mb_shape, xs.dtype), (stage_axis,), to="varying"
        )
        outputs = jax.lax.pcast(
            jnp.zeros((n_mb,) + mb_shape, xs.dtype), (stage_axis,), to="varying"
        )
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(state, t):
            carry, outputs = state
            inject = xs[jnp.clip(t, 0, n_mb - 1)]
            x_in = jnp.where(stage == 0, inject, carry)
            y = stage_fn(p_mine, x_in)
            # pass to next stage; wraps last->0 but stage 0 ignores it
            carry_next = jax.lax.ppermute(y, stage_axis, perm)
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
            emit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, y.astype(outputs.dtype), out_idx, 0
            )
            outputs = jnp.where(emit, updated, outputs)
            return (carry_next, outputs), None

        (carry, outputs), _ = jax.lax.scan(
            tick, (carry, outputs), jnp.arange(ticks)
        )
        # broadcast the last stage's outputs to every shard
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            stage_axis,
        )
        return outputs

    return jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        axis_names={stage_axis},
    )(params_stacked, x_microbatches)


def pipeline_loss(stage_fn, params_stacked, x_mbs, y_mbs, mesh, stage_axis="stage"):
    """Mean-squared-error training objective through the pipeline (demo)."""
    out = gpipe(stage_fn, params_stacked, x_mbs, mesh, stage_axis)
    return jnp.mean((out - y_mbs) ** 2)
