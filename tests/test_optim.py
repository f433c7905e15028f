"""Optimizer substrate: AdamW, schedules, int8 EF compression."""

import numpy as np

import jax
import jax.numpy as jnp

from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    compressed_psum,
    dequantize_int8,
    ef_init,
    quantize_int8,
    warmup_cosine,
)

from helpers import run_multidevice


def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0]), "b": jnp.array([2.0])}
    cfg = AdamWConfig(learning_rate=0.1, weight_decay=0.0)
    state = adamw_init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    for _ in range(300):
        grads = jax.grad(loss)(params)
        params, state, _ = adamw_update(grads, state, params, cfg)
    assert float(loss(params)) < 1e-3


def test_grad_clip_bounds_update():
    params = {"w": jnp.zeros(3)}
    cfg = AdamWConfig(learning_rate=1.0, grad_clip=1.0, weight_decay=0.0)
    state = adamw_init(params)
    huge = {"w": jnp.full(3, 1e9)}
    _, _, stats = adamw_update(huge, state, params, cfg)
    assert float(stats["grad_norm"]) > 1e8  # norm observed pre-clip


def test_bf16_params_fp32_moments():
    params = {"w": jnp.zeros(4, jnp.bfloat16)}
    state = adamw_init(params)
    assert state["m"]["w"].dtype == jnp.float32
    new_p, state, _ = adamw_update(
        {"w": jnp.ones(4, jnp.bfloat16)}, state, params, AdamWConfig()
    )
    assert new_p["w"].dtype == jnp.bfloat16


def test_warmup_cosine_shape():
    sched = warmup_cosine(1.0, warmup_steps=10, total_steps=100)
    assert float(sched(jnp.int32(0))) == 0.0
    assert abs(float(sched(jnp.int32(10))) - 1.0) < 0.11
    assert float(sched(jnp.int32(100))) <= 0.11


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, 1000), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased_over_steps():
    """EF property: accumulated quantization error stays bounded (the bias
    doesn't grow), so the long-run average update is the true gradient."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(0, 1, 256), jnp.float32)
    ef = jnp.zeros(256)
    applied = jnp.zeros(256)
    for _ in range(50):
        c = g_true + ef
        q, s = quantize_int8(c)
        deq = dequantize_int8(q, s)
        applied = applied + deq
        ef = c - deq
    avg = applied / 50
    np.testing.assert_allclose(np.asarray(avg), np.asarray(g_true), atol=2e-2)


def test_compressed_psum_multidevice():
    out = run_multidevice(
        """
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim import compressed_psum, ef_init

        from repro import compat
        mesh = compat.make_mesh((4,), ("pod",))
        g = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")))
        def sync(g_loc, ef_loc):
            gr = {"w": g_loc[0]}
            efr = {"w": ef_loc[0]}
            avg, ef2 = compressed_psum(gr, efr, "pod", 4)
            return avg["w"][None], ef2["w"][None]

        ef = np.zeros_like(g)
        avg, ef2 = jax.jit(sync)(g, ef)
        true_avg = g.mean(axis=0)
        err = np.abs(np.asarray(avg)[0] - true_avg).max()
        assert err < 0.05, err
        # int8 collective visible in HLO
        hlo = jax.jit(sync).lower(g, ef).compile().as_text()
        assert "s32" in hlo or "s8" in hlo
        print("OK", err)
        """,
        devices=4,
    )
    assert "OK" in out
