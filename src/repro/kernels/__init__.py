"""Pallas TPU kernels for the framework's compute hot spots.

The paper's contribution is communication-level (no custom compute kernel),
so this package covers the compute on either side of the all-to-all:

* ``flash_attention`` — blockwise online-softmax attention (32k prefill).
* ``grouped_matmul`` — per-expert GEMM over token buckets (MoE FFN).
* ``rmsnorm`` — fused normalization.
* ``routed_expert_ffn`` — decode-sized MoE FFN that reads only the routed
  experts' weights (``moe_decode.py``).

Layout: ``<name>.py`` holds the ``pl.pallas_call`` kernel with explicit
BlockSpec VMEM tiling; ``ops.py`` is the backend-dispatching jit wrapper;
``ref.py`` the pure-jnp oracle. Tests sweep shapes/dtypes in interpret mode.
"""

from .ops import flash_attention, grouped_matmul, kernel_backend, rmsnorm, routed_expert_ffn

__all__ = ["flash_attention", "grouped_matmul", "kernel_backend", "rmsnorm", "routed_expert_ffn"]
