"""MoE layer: gating, capacity, local-vs-distributed equivalence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.moe import _gate, moe_apply, moe_init

from helpers import run_multidevice

CFG = get_config("mixtral-8x7b").reduced()  # 4 experts, top-2


def _params(cfg, seed=0):
    return moe_init(jax.random.PRNGKey(seed), cfg, jnp.float32)


def test_gate_counts_and_weights():
    params = _params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, CFG.d_model))
    idx, w, aux, counts = _gate(x, params["router"], CFG)
    assert idx.shape == (64, 2) and w.shape == (64, 2)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert int(counts.sum()) == 64 * 2
    assert float(aux) >= 1.0 - 1e-6  # aux loss >= 1 (uniform optimum)


def test_moe_apply_shapes_and_counts():
    params = _params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, CFG.d_model))
    out, aux, counts = moe_apply(params, CFG, x)
    assert out.shape == x.shape
    assert counts.shape == (CFG.num_experts,)
    assert int(counts.sum()) == 2 * 32 * CFG.experts_per_token
    assert bool(jnp.all(jnp.isfinite(out)))


def test_dense_small_path_no_drops():
    """Decode-sized inputs take the dense path: identical token counts in
    == weighted expert mix out, no capacity drops."""
    params = _params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 1, CFG.d_model))
    out, _aux, counts = moe_apply(params, CFG, x)
    assert out.shape == x.shape
    assert int(counts.sum()) == 3 * CFG.experts_per_token


def test_capacity_dropping_monotone():
    """Lower capacity factor -> no more output mass (dropped tokens)."""
    import dataclasses

    params = _params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, CFG.d_model))
    hi = dataclasses.replace(CFG, capacity_factor=8.0)
    lo = dataclasses.replace(CFG, capacity_factor=0.25)
    out_hi, _, _ = moe_apply(params, hi, x)
    out_lo, _, _ = moe_apply(params, lo, x)
    assert float(jnp.abs(out_lo).sum()) <= float(jnp.abs(out_hi).sum()) + 1e-3


def test_high_capacity_matches_dense_reference():
    """With capacity high enough to never drop, the dispatch path must equal
    the dense-EP reference computation exactly."""
    import dataclasses

    from repro.models.moe import _moe_dense_small

    cfg = dataclasses.replace(CFG, capacity_factor=float(CFG.num_experts))
    params = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, cfg.d_model))
    out_dispatch, _, _ = moe_apply(params, cfg, x)
    out_dense, _, _ = _moe_dense_small(x.reshape(32, -1), params, cfg)
    np.testing.assert_allclose(
        np.asarray(out_dispatch).reshape(32, -1), np.asarray(out_dense),
        atol=2e-4, rtol=2e-4,
    )


@pytest.mark.parametrize("mode", ["dense", "rails", "spray", "ring"])
def test_distributed_matches_local(mode):
    """shard_map EP path == single-device path, for every dispatch mode."""
    out = run_multidevice(
        f"""
        import numpy as np, dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_config
        from repro.models.moe import moe_apply, moe_init, EpInfo

        cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                                  dispatch_mode="{mode}", num_rails=2,
                                  dispatch_chunks=2)
        params = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 64, cfg.d_model))
        ref, _, ref_counts = moe_apply(params, cfg, x)

        from repro import compat
        mesh = compat.make_mesh((2, 4), ("data", "expert"))
        ep = EpInfo(mesh, "expert", 4)
        with mesh:
            out, _, counts = jax.jit(
                lambda p, xx: moe_apply(p, cfg, xx, ep)
            )(params, x)
        err = float(jnp.abs(out - ref).max())
        assert err < 2e-4, err
        assert (np.asarray(counts) == np.asarray(ref_counts)).all()
        print("OK", err)
        """,
        devices=8,
    )
    assert "OK" in out


# -- decode-sized batches: the routed-expert kernel -----------------------------


@pytest.mark.parametrize("n", [1, 4, 7])
def test_dense_small_kernel_matches_einsum(monkeypatch, n):
    """With the experts on one shard the decode path runs the routed-expert
    kernel (interpret mode here); it equals the every-expert einsum that
    the expert-sharded path keeps."""
    from repro.models.moe import _moe_dense_small

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    params = _params(CFG)
    x = jax.random.normal(jax.random.PRNGKey(6), (n, CFG.d_model))
    got, aux, counts = _moe_dense_small(x, params, CFG)
    want, want_aux, want_counts = _moe_dense_small(x, params, CFG, ep=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert float(aux) == float(want_aux)
    assert (np.asarray(counts) == np.asarray(want_counts)).all()


def test_decode_experts_read_counts_routed_experts(monkeypatch):
    """``experts_read`` of a one-layer decode step is the kernel's
    ``n_active``: the experts some token routes to."""
    import dataclasses

    from repro.models import decode_fn, init_cache, init_params

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    cfg = dataclasses.replace(CFG, num_layers=1, num_experts=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jnp.asarray([[3], [11]], jnp.int32)
    _logits, _cache, counts, read = decode_fn(
        params, cfg, init_cache(cfg, 2, 8), tok, 0, return_counts=True
    )
    assert int(read) == np.count_nonzero(np.asarray(counts))
    assert 2 <= int(read) <= 4


def test_dense_small_keeps_einsum_under_expert_parallelism():
    """With the expert weights sharded (ep > 1) the decode path keeps the
    every-expert einsum, no kernel, and equals the one-shard kernel path."""
    out = run_multidevice(
        """
        import os
        os.environ["REPRO_PALLAS"] = "interpret"
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_config
        from repro.models.moe import moe_apply, moe_init, EpInfo

        cfg = get_config("mixtral-8x7b").reduced()
        params = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(7), (4, 1, cfg.d_model))
        one = lambda p, xx: moe_apply(p, cfg, xx)
        assert "pallas_call" in str(jax.make_jaxpr(one)(params, x))
        ref, _, ref_counts = one(params, x)

        mesh = compat.make_mesh((1, 4), ("data", "expert"))
        sharded = jax.jit(lambda p, xx: moe_apply(p, cfg, xx, EpInfo(mesh, "expert", 4)))
        with jax.set_mesh(mesh):
            assert "pallas_call" not in str(jax.make_jaxpr(sharded)(params, x))
            out, _, counts = sharded(params, x)
        err = float(jnp.abs(out - ref).max())
        assert err < 2e-4, err
        assert (counts == ref_counts).all()
        print("OK", err)
        """,
        devices=4,
    )
    assert "OK" in out
