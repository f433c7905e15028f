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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compat import make_mesh
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
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


@pytest.mark.parametrize("rows", [2048, 4])
def test_rmsnorm_fwd_and_vjp_compile(one_chip, rows):
    """Prefill-sized (2048) and decode-sized (4) rows of d_model 4096."""
    x = jax.ShapeDtypeStruct((rows, MIXTRAL.d_model), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((MIXTRAL.d_model,), jnp.bfloat16, sharding=one_chip)

    def loss(x, w):
        return jnp.sum(rmsnorm_pallas(x, w, MIXTRAL.rms_eps).astype(jnp.float32))

    hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1)), x, w)
    assert "tpu_custom_call" in hlo


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
