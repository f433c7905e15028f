"""Pallas TPU kernel for the decode-sized MoE: read only the routed experts.

At decode a step holds a handful of tokens, each routed to ``k`` of ``E``
experts, and the layer's time is the time to read expert weights from
HBM. Computing every expert for every token (and multiplying the unrouted
ones by a zero gate weight) reads all ``E`` experts' weights; this kernel
reads those of the experts some token routes to, and no others.

``x: (n, d)`` tokens, ``gates: (n, E)`` per-token gate weights (zero where
the token does not route to the expert), ``w_gate``/``w_up: (E, d, f)``,
``w_down: (E, f, d)``. ``order`` (``(E,)`` int32) lists the experts in use
first, then padding slots that repeat the last expert in use, and
``n_active`` (``(1,)`` int32) counts the experts in use; both are scalar
prefetch, so the weight DMAs follow the step's routes.

The weights may instead be stacked over layers, ``(L, E, d, f)``, with a
``layer`` index, also scalar prefetch. A decode step's layer scan hands
the stack over whole: a layer's slice of it that fed the kernel would be
copied out of the stack first, which reads and writes every expert.

* The grid is ``(E slots, f / block_f)``. Step ``(s, j)`` streams
  ``w_gate[order[s], :, j]``, ``w_up[order[s], :, j]`` and
  ``w_down[order[s], j, :]`` (of the layer's experts), computes
  ``act(x @ wg) * (x @ wu)``, scales
  each row by its token's gate weight for expert ``order[s]``, and adds
  its product with the ``w_down`` tile into an ``(n, d)`` float32 VMEM
  accumulator that lives across the whole grid. The output is written
  once, at the last step.
* Padding slots (``s >= n_active``) map every weight to the block of the
  last real step (expert ``order[n_active - 1]``, last ``f`` tile). The
  block index does not change, so Pallas issues no DMA, and the compute
  is masked off.

**Backward.** ``routed_expert_ffn_pallas`` carries a ``jax.custom_vjp``
whose backward is ``jax.vjp`` of ``ref.routed_expert_ffn_ref`` (every
expert, dense): a tiny training batch that lands on this path still
differentiates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import routed_expert_ffn_ref

__all__ = ["routed_order", "routed_expert_ffn_pallas"]

# Bytes of one weight tile. On a TPU v5e at Mixtral widths, tiles of
# 7-16 MiB stream expert weights at 734-755 GB/s, 4 MiB at 702-723 and
# 2 MiB at 699-736 (the kernel alone, 2, 5 or 8 of 8 experts read).
_TILE_BYTES = 8 << 20
# Rows padded to a bfloat16 sublane tile.
_ROWS = 16


def routed_order(counts: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(order, n_active)`` from one layer's per-expert routed-token counts.

    ``order`` lists the experts with a nonzero count first, in expert
    order, then repeats the last of them in every remaining slot;
    ``n_active`` (``(1,)`` int32) is how many have a nonzero count.
    """
    used = counts > 0
    n_active = jnp.sum(used, dtype=jnp.int32)
    order = jnp.argsort(~used, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_active - 1, 0)]
    order = jnp.where(jnp.arange(order.shape[0]) < n_active, order, last)
    return order, n_active[None]


def _block_f(d: int, f: int, itemsize: int) -> int:
    """The widest lane-aligned divisor of ``f`` whose ``(d, block_f)``
    weight tile stays within ``_TILE_BYTES``; ``f`` itself if it is not
    lane-aligned (a whole-dimension block)."""
    fits = [b for b in range(128, f + 1, 128) if f % b == 0 and d * b * itemsize <= _TILE_BYTES]
    if fits:
        return max(fits)
    return 128 if f % 128 == 0 else f


def _routed_ffn_kernel(order_ref, n_active_ref, layer_ref, x_ref, gates_ref, wg_ref, wu_ref,
                       wd_ref, o_ref, acc_ref, *, act: str):
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < n_active_ref[0])
    def _accumulate():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate)) * up
        # This expert's gate weight for each token: a lane select of gates.
        g = gates_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
        w = jnp.sum(jnp.where(lane == order_ref[s], g, 0.0), axis=1, keepdims=True)
        acc_ref[...] += jnp.dot((h * w).astype(wd_ref.dtype), wd_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when((s == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _routed_forward(x, gates, w_gate, w_up, w_down, order, n_active, layer, act, block_f,
                    interpret):
    n, d = x.shape
    _, e, _, f = w_gate.shape
    bf = block_f or _block_f(d, f, w_gate.dtype.itemsize)
    if f % bf:
        raise ValueError(f"block_f {bf} does not divide the FFN width {f}")
    nf = f // bf
    rows = -(-n // _ROWS) * _ROWS
    xp = jnp.pad(x, ((0, rows - n), (0, 0)))
    gp = jnp.pad(gates.astype(jnp.float32), ((0, rows - n), (0, 0)))

    def f_tile(s, j, n_active_ref):
        # Padding slots keep the last real step's tile: no new DMA.
        return jnp.where(s < n_active_ref[0], j, nf - 1)

    def whole(s, j, order_ref, n_active_ref, layer_ref):
        return 0, 0

    def in_proj(s, j, order_ref, n_active_ref, layer_ref):
        return layer_ref[0], order_ref[s], 0, f_tile(s, j, n_active_ref)

    def out_proj(s, j, order_ref, n_active_ref, layer_ref):
        return layer_ref[0], order_ref[s], f_tile(s, j, n_active_ref), 0

    tile_bytes = d * bf * w_gate.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_routed_ffn_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(e, nf),
            in_specs=[
                pl.BlockSpec((rows, d), whole),
                pl.BlockSpec((rows, e), whole),
                pl.BlockSpec((None, None, d, bf), in_proj),
                pl.BlockSpec((None, None, d, bf), in_proj),
                pl.BlockSpec((None, None, bf, d), out_proj),
            ],
            out_specs=pl.BlockSpec((rows, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # Both axes carry the accumulator.
            dimension_semantics=("arbitrary", "arbitrary"),
            # Three double-buffered weight tiles, plus room for the rest.
            vmem_limit_bytes=6 * tile_bytes + (16 << 20),
        ),
        interpret=interpret,
    )(order, n_active, layer, xp, gp, w_gate, w_up, w_down)
    return out[:n]


def routed_expert_ffn_pallas(
    x: jnp.ndarray,
    gates: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    order: jnp.ndarray,
    n_active: jnp.ndarray,
    layer: jnp.ndarray | None = None,
    *,
    act: str = "silu",
    block_f: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gate-weighted sum of the routed experts' FFNs, ``(n, d)``, matching
    ``ref.routed_expert_ffn_ref`` where ``order``/``n_active`` (from
    :func:`routed_order`) cover every expert with a nonzero gate weight.
    With ``layer`` (an int32 index) the weights are stacked over layers,
    ``(L, E, d, f)``, and the kernel reads that layer's experts.
    ``block_f`` (a divisor of ``f``) defaults to the widest lane-aligned
    tile of at most ``_TILE_BYTES`` per weight.

    Differentiable in every float operand: the backward is ``jax.vjp`` of
    the reference.
    """
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    return _routed_vjp(x, gates, w_gate, w_up, w_down, order, n_active, layer,
                       (act, block_f, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _routed_vjp(x, gates, w_gate, w_up, w_down, order, n_active, layer, opts):
    return _routed_forward(x, gates, w_gate, w_up, w_down, order, n_active, layer, *opts)


def _routed_vjp_fwd(x, gates, w_gate, w_up, w_down, order, n_active, layer, opts):
    out = _routed_forward(x, gates, w_gate, w_up, w_down, order, n_active, layer, *opts)
    return out, (x, gates, w_gate, w_up, w_down, layer)


def _routed_vjp_bwd(opts, residuals, d_out):
    *operands, layer = residuals

    def ffn(x, gates, *stacks):
        weights = (jax.lax.dynamic_index_in_dim(w, layer[0], keepdims=False) for w in stacks)
        return routed_expert_ffn_ref(x, gates, *weights, act=opts[0])

    _, vjp = jax.vjp(ffn, *operands)
    return (*vjp(d_out), None, None, None)


_routed_vjp.defvjp(_routed_vjp_fwd, _routed_vjp_bwd)
