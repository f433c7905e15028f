"""Pallas TPU fused RMSNorm kernel.

Row-block kernel: grid over token blocks, each block ``(block_rows, D)`` is
normalized in one VMEM-resident pass (fp32 mean-square + rsqrt + scale) —
fuses what XLA would otherwise emit as several HBM round trips.

**Backward.** ``rmsnorm_pallas`` carries a ``jax.custom_vjp``: the forward
is the kernel, the backward is :func:`rmsnorm_bwd`, an explicit ``jnp``
recomputation of the row statistic in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rmsnorm_bwd", "rmsnorm_pallas"]


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def rmsnorm_pallas(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-6,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused RMSNorm matching ``ref.rmsnorm_ref``. ``x: (..., D)``.

    Differentiable in ``x`` and ``weight``: the backward is
    :func:`rmsnorm_bwd`.
    """
    return _rmsnorm_vjp(x, weight, (eps, block_rows, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_vjp(x, weight, opts):
    return _rmsnorm_forward(x, weight, *opts)


def _rmsnorm_vjp_fwd(x, weight, opts):
    return _rmsnorm_forward(x, weight, *opts), (x, weight)


def _rmsnorm_vjp_bwd(opts, residuals, d_out):
    x, weight = residuals
    return rmsnorm_bwd(x, weight, d_out, opts[0])


_rmsnorm_vjp.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)


def rmsnorm_bwd(x, weight, d_out, eps: float):
    """Gradients ``(dx, dweight)`` of ``x * rsqrt(mean(x^2) + eps) * w``.

    With ``r = rsqrt(mean(x^2) + eps)`` and ``g = dy * w``:
    ``dx = r * g - x * r^3 * mean(g * x)`` and ``dw = sum_rows(dy * x * r)``.
    """
    xf = x.astype(jnp.float32)
    dy = d_out.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    g = dy * weight.astype(jnp.float32)
    dx = r * g - xf * r**3 * jnp.mean(g * xf, axis=-1, keepdims=True)
    dw = jnp.sum((dy * xf * r).reshape(-1, x.shape[-1]), axis=0)
    return dx.astype(x.dtype), dw.astype(weight.dtype)


def _rmsnorm_forward(x, weight, eps, block_rows, interpret):
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    rows = x2.shape[0]
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, weight)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
