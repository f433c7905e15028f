"""Compile-only checks for a TPU v5e, with no chip attached.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (unaligned
kernel slices, f64 kernel operands, kernels XLA would have to partition,
programs that do not fit). Every case lowers and compiles one program of
the chip's main path at real widths; nothing runs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the test
workers all import this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compat import make_mesh
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_decode import routed_expert_ffn_pallas, routed_order
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.netsim import devicesim

MIXTRAL = get_config("mixtral-8x7b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # Loading the TPU library for the description would otherwise write
    # its compiler logs to a fixed directory outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_fwd_and_vjp_compile(one_chip):
    """Mixtral widths: B=1, T=2048, 32 query / 8 KV heads of 128, window 4096."""
    b, t, hd = 1, 2048, MIXTRAL.head_dim
    q = jax.ShapeDtypeStruct((b, t, MIXTRAL.num_heads, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, MIXTRAL.num_kv_heads, hd), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention_pallas(q, k, v, window=MIXTRAL.sliding_window)
        return jnp.sum(out.astype(jnp.float32))

    hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert "tpu_custom_call" in hlo


TRAINING_ATTENTION = [
    # (config, batch, seq, attention options)
    ("mixtral-8x7b", 2, 1024, {}),  # train-ep4-rails: batch 8 over 4 chips
    ("phi4-mini-3.8b", 1, 4096, {}),  # a group of 3 query heads a KV head
    ("gemma2-9b", 1, 4096, {"softcap": 50.0, "window": 4096}),  # head_dim 256
]


@pytest.mark.parametrize("case", TRAINING_ATTENTION, ids=lambda c: c[0])
def test_flash_attention_training_shape_is_one_three_operand_kernel(one_chip, case):
    """A per-device training call at the configuration's widths with the
    shape-chosen tiles: forward and VJP compile, so the tiles meet the
    chip's block tiling and fit in VMEM, and the forward is exactly one
    Pallas call with three operands (q, k, v), the call the benchmark's
    ``train.attn_kernel_roofline`` picks out of a trace."""
    name, b, t, kw = case
    cfg = get_config(name)
    hd = cfg.head_dim
    q = jax.ShapeDtypeStruct((b, t, cfg.num_heads, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, cfg.num_kv_heads, hd), jnp.bfloat16, sharding=one_chip)

    def attn(q, k, v):
        return flash_attention_pallas(q, k, v, **kw)

    fwd = _hlo(attn, q, kv, kv)
    calls = re.findall(r"custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"", fwd)
    assert [c.count("%") for c in calls] == [3]

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    assert "tpu_custom_call" in _hlo(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("rows", [2048, 4])
def test_rmsnorm_fwd_and_vjp_compile(one_chip, rows):
    """Prefill-sized (2048) and decode-sized (4) rows of d_model 4096."""
    x = jax.ShapeDtypeStruct((rows, MIXTRAL.d_model), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((MIXTRAL.d_model,), jnp.bfloat16, sharding=one_chip)

    def loss(x, w):
        return jnp.sum(rmsnorm_pallas(x, w, MIXTRAL.rms_eps).astype(jnp.float32))

    hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1)), x, w)
    assert "tpu_custom_call" in hlo


def test_routed_expert_ffn_compiles_on_layer_stack(one_chip):
    """Mixtral decode MoE: 4 tokens, 8 experts of 14336 stacked over 4
    layers. The kernel reads the stack in place: no layer's experts are
    copied out of it (2.8 GB each)."""
    n, e, d, f = 4, MIXTRAL.num_experts, MIXTRAL.d_model, MIXTRAL.moe_d_ff

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def ffn(x, gates, wg, wu, wd, counts, layer):
        return routed_expert_ffn_pallas(x, gates, wg, wu, wd, *routed_order(counts), layer)

    compiled = jax.jit(ffn).lower(
        arg((n, d)), arg((n, e)), arg((4, e, d, f)), arg((4, e, d, f)), arg((4, e, f, d)),
        arg((e,), jnp.int32), arg((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_routed_expert_ffn_vjp_compiles(one_chip):
    """One layer's experts at Mixtral widths, forward and backward."""
    n, e, d, f = 4, MIXTRAL.num_experts, MIXTRAL.d_model, MIXTRAL.moe_d_ff

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, gates, wg, wu, wd, counts):
        out = routed_expert_ffn_pallas(x, gates, wg, wu, wd, *routed_order(counts))
        return jnp.sum(out.astype(jnp.float32))

    hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), arg((n, d)), arg((n, e)),
               arg((e, d, f)), arg((e, d, f)), arg((e, f, d)), arg((e,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_mixtral_decode_step_reads_experts_in_place(topo, monkeypatch):
    """The decode step at Mixtral widths (2 layers, batch 4) hands the
    layer-stacked expert weights to the routed kernel whole: its scratch
    holds no layer's experts (2.8 GB each)."""
    import dataclasses

    from repro.launch.steps import make_decode_step
    from repro.models import init_cache, init_params
    from repro.parallel.mesh_view import build_mesh_context
    from repro.parallel.sharding import param_shardings

    monkeypatch.setattr(ops, "kernel_backend", lambda: "pallas")
    cfg = dataclasses.replace(MIXTRAL, num_layers=2)
    ctx = build_mesh_context(make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1]), cfg)
    one = SingleDeviceSharding(topo.devices[0])
    with jax.set_mesh(ctx.mesh):
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                              params, param_shardings(cfg, ctx, params))
        cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
                             jax.eval_shape(lambda: init_cache(cfg, 4, 256)))
        tokens = {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one)}
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        compiled = jax.jit(make_decode_step(cfg, ctx), donate_argnums=(1,)).lower(
            params, cache, tokens, pos).compile()
    assert "moe/experts/pallas_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_kernels_compile_per_device_on_four_chips(topo, monkeypatch):
    """Under a four-chip mesh the kernels run per device in a shard_map
    (XLA cannot partition a Mosaic kernel), forward and backward."""
    monkeypatch.setattr(ops, "kernel_backend", lambda: "pallas")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    seq = NamedSharding(mesh, P(None, "model"))  # sequence-parallel residual
    x = jax.ShapeDtypeStruct((4, 512, MIXTRAL.d_model), jnp.bfloat16, sharding=seq)
    w = jax.ShapeDtypeStruct((MIXTRAL.d_model,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    hd = MIXTRAL.head_dim

    def loss(x, w):
        h = ops.rmsnorm(x, w, MIXTRAL.rms_eps)
        q = h.reshape(4, 512, MIXTRAL.num_heads, hd)
        kv = h[..., : MIXTRAL.num_kv_heads * hd].reshape(4, 512, MIXTRAL.num_kv_heads, hd)
        return jnp.sum(ops.flash_attention(q, kv, kv).astype(jnp.float32))

    with jax.set_mesh(mesh):
        hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1)), x, w)
    assert "tpu_custom_call" in hlo


def test_device_simulator_scan_compiles_with_tpu_impl(one_chip):
    """The scan at the smallest bucket with the implementation the TPU
    runs (``lax``: the f64 operands keep the lane kernel off the chip)."""
    f, levels, links = devicesim.MIN_BUCKET, 4, 256
    with jax.enable_x64(True):
        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        lowered = devicesim._scan_single_jit.lower(
            arg((f, levels), jnp.int32), arg((f,), jnp.float64),
            arg((f,), jnp.float64), arg((f,), jnp.int64),
            arg((links,), jnp.float64), arg((links,), jnp.float64),
            arg((links,), jnp.float64), arg((f,), jnp.bool_),
            arg((), jnp.float64), impl="lax", lane_depth=0,
        )
        hlo = lowered.compile().as_text()
    assert "sort" in hlo and "tpu_custom_call" not in hlo
