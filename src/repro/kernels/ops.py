"""Backend-dispatching wrappers for the Pallas kernels.

On TPU the Pallas kernels always run natively (with their custom VJPs, so
training differentiates through them); everywhere else the pure-jnp oracle
(ref.py) executes — same semantics, so model code calls these
unconditionally. Off the TPU, ``REPRO_PALLAS=interpret`` runs the Pallas
kernels in interpret mode (a CPU-test setting).

XLA cannot partition a Mosaic kernel, so under a multi-device mesh (set
with ``jax.set_mesh``) each kernel call runs in a ``shard_map`` over the
mesh's automatic axes: on each device's block of the batch when the
batch divides over them, else (attention) of the heads, else on the whole
replicated operands.
"""

from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .flash_attention import flash_attention_pallas
from .grouped_matmul import grouped_matmul_pallas
from .ref import flash_attention_ref, grouped_matmul_ref, rmsnorm_ref
from .rmsnorm import rmsnorm_pallas

__all__ = ["flash_attention", "grouped_matmul", "rmsnorm", "kernel_backend"]


def kernel_backend() -> str:
    """``pallas`` on TPU whatever the environment says; elsewhere ``ref``,
    or ``interpret`` under ``REPRO_PALLAS=interpret``."""
    if jax.default_backend() == "tpu":
        return "pallas"
    return "interpret" if os.environ.get("REPRO_PALLAS") == "interpret" else "ref"


def _on_local_blocks(kernel, operands, split_dims):
    """``kernel(*operands)``, per device under a multi-device mesh.

    ``split_dims(n)`` gives, for ``n`` devices, the dim of each operand
    to split over them (``None`` = replicated) and the output's dim.
    """
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    n = math.prod(mesh.shape[a] for a in axes)
    if n == 1:
        return kernel(*operands)
    in_dims, out_dim = split_dims(n)

    def spec(x, dim):
        return P(*(axes if i == dim else None for i in range(x.ndim)))

    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=tuple(spec(x, d) for x, d in zip(operands, in_dims)),
        out_specs=spec(operands[0], out_dim),
        axis_names=set(axes),
        # pallas_call's out_shape carries no varying-axes type; gradients
        # of replicated operands are still summed over the devices.
        check_vma=False,
    )(*operands)


def flash_attention(
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
) -> jnp.ndarray:
    backend = kernel_backend()
    if backend in ("pallas", "interpret"):
        def kernel(q, k, v):
            return flash_attention_pallas(
                q,
                k,
                v,
                causal=causal,
                q_offset=q_offset,
                window=window,
                softcap=softcap,
                scale=scale,
                block_q=block_q,
                block_k=block_k,
                interpret=backend == "interpret",
            )

        def split_dims(n):
            # Batch, else heads (q and kv heads split alike keep each GQA
            # group on one device), else replicated.
            if q.shape[0] % n == 0:
                return (0, 0, 0), 0
            if q.shape[2] % n == 0 and k.shape[2] % n == 0:
                return (2, 2, 2), 2
            return (None, None, None), None

        return _on_local_blocks(kernel, (q, k, v), split_dims)
    return flash_attention_ref(
        q,
        k,
        v,
        causal=causal,
        q_offset=q_offset,
        window=window,
        softcap=softcap,
        scale=scale,
        block_k=block_k,
    )


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    backend = kernel_backend()
    if backend in ("pallas", "interpret"):
        return grouped_matmul_pallas(x, w, interpret=backend == "interpret")
    return grouped_matmul_ref(x, w)


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    backend = kernel_backend()
    if backend in ("pallas", "interpret"):
        def kernel(x, weight):
            return rmsnorm_pallas(x, weight, eps, interpret=backend == "interpret")

        def split_dims(n):
            dim = 0 if x.ndim > 1 and x.shape[0] % n == 0 else None
            return (dim, None), dim

        return _on_local_blocks(kernel, (x, weight), split_dims)
    return rmsnorm_ref(x, weight, eps)
