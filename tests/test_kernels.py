"""Pallas kernel sweeps: interpret-mode vs pure-jnp oracle (ref.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.ref import flash_attention_ref, grouped_matmul_ref, rmsnorm_ref
from repro.kernels.rmsnorm import rmsnorm_pallas


def _naive_attention(q, k, v, causal=True, q_offset=0, window=None, softcap=None):
    b, t, h, hd = q.shape
    s = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv
    qf = q.astype(jnp.float32).reshape(b, t, hkv, rep, hd) * hd**-0.5
    scores = jnp.einsum("bthrd,bshd->bhrts", qf, k.astype(jnp.float32))
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    qp = q_offset + jnp.arange(t)
    kp = jnp.arange(s)
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window:
        mask &= qp[:, None] - kp[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrts,bshd->bthrd", p, v.astype(jnp.float32))
    return out.reshape(b, t, h, hd).astype(q.dtype)


FLASH_CASES = [
    # (b, t, s, h, hkv, hd, kwargs)
    (2, 64, 64, 4, 2, 32, {}),
    (1, 32, 96, 4, 4, 64, {"q_offset": 64}),
    (2, 64, 64, 8, 2, 32, {"window": 17}),
    (1, 64, 64, 2, 1, 32, {"causal": False}),
    (2, 64, 64, 4, 2, 32, {"softcap": 30.0}),
    (1, 1, 40, 4, 2, 32, {"q_offset": 39}),  # decode
    (1, 50, 50, 2, 2, 16, {}),  # non-multiple-of-block sizes
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    b, t, s, h, hkv, hd, kw = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), dtype)
    got = flash_attention_pallas(q, k, v, block_q=32, block_k=32, interpret=True, **kw)
    want = flash_attention_ref(q, k, v, block_k=48, **kw)
    oracle = _naive_attention(q, k, v, **kw)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), oracle.astype(jnp.float32), atol=tol, rtol=tol
    )
    np.testing.assert_allclose(
        want.astype(jnp.float32), oracle.astype(jnp.float32), atol=tol, rtol=tol
    )


GMM_CASES = [(1, 64, 32, 48), (4, 100, 64, 72), (8, 33, 17, 129)]


@pytest.mark.parametrize("g,n,k,m", GMM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_oracle(g, n, k, m, dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(g, n, k)), dtype)
    w = jnp.asarray(rng.normal(size=(g, k, m)), dtype)
    got = grouped_matmul_pallas(x, w, block_n=32, block_m=32, block_k=32, interpret=True)
    want = grouped_matmul_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("shape", [(7, 64), (3, 5, 128), (256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_oracle(shape, dtype):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=shape[-1:]), dtype)
    got = rmsnorm_pallas(x, w, 1e-6, block_rows=16, interpret=True)
    want = rmsnorm_ref(x, w, 1e-6)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=2e-2, rtol=2e-2
    )


def test_ops_dispatch_env(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS", "off")
    assert ops.kernel_backend() == "ref"
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    assert ops.kernel_backend() == "interpret"
    monkeypatch.setenv("REPRO_PALLAS", "auto")
    assert ops.kernel_backend() in ("ref", "pallas")


# -- custom VJPs: the kernels' backward passes against jax.grad of the oracles


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vjp_matches_oracle_grad(case):
    b, t, s, h, hkv, hd, kw = case
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=shape), jnp.float32)
        for shape in ((b, t, h, hd), (b, s, hkv, hd), (b, s, hkv, hd))
    )
    probe = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * probe)

    got = jax.grad(
        loss(lambda q, k, v: flash_attention_pallas(
            q, k, v, block_q=32, block_k=32, interpret=True, **kw
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        loss(lambda q, k, v: flash_attention_ref(q, k, v, block_k=48, **kw)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(7, 64), (3, 5, 128), (256, 256)])
def test_rmsnorm_vjp_matches_oracle_grad(shape):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    w = jnp.asarray(rng.normal(size=shape[-1:]), jnp.float32)
    probe = jnp.asarray(rng.normal(size=shape), jnp.float32)

    def loss(norm):
        return lambda x, w: jnp.sum(norm(x, w, 1e-6) * probe)

    got = jax.grad(
        loss(lambda x, w, eps: rmsnorm_pallas(x, w, eps, block_rows=16, interpret=True)),
        argnums=(0, 1),
    )(x, w)
    want = jax.grad(loss(rmsnorm_ref), argnums=(0, 1))(x, w)
    for g, ref in zip(got, want):
        np.testing.assert_allclose(g, ref, atol=1e-4, rtol=1e-4)


# -- the per-device kernel path under a multi-device mesh ---------------------

_MESH_GRAD_CHILD = """
import os
os.environ["REPRO_PALLAS"] = "interpret"
import numpy as np
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.kernels import ops
from repro.kernels.ref import flash_attention_ref, rmsnorm_ref

rng = np.random.default_rng(5)
def arr(*shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)

if {kernel!r} == "flash":
    b, h, hkv, t, hd = {shape}
    args = (arr(b, t, h, hd), arr(b, t, hkv, hd), arr(b, t, hkv, hd))
    probe = arr(b, t, h, hd)
    fn = lambda q, k, v: ops.flash_attention(q, k, v, window=40, block_q=16, block_k=16)
    oracle = lambda q, k, v: flash_attention_ref(q, k, v, window=40)
else:
    shape = {shape}
    args = (arr(*shape), 1.0 + 0.1 * arr(shape[-1]))
    probe = arr(*shape)
    fn = lambda x, w: ops.rmsnorm(x, w, 1e-6)
    oracle = lambda x, w: rmsnorm_ref(x, w, 1e-6)

def grads(f):
    loss = lambda *a: jnp.sum(f(*a) * probe)
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(len(args)))))

one_val, one = grads(fn)(*args)  # no mesh: the kernel on one device
ref_val, ref = grads(oracle)(*args)
mesh = make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    assert "shard_map" in str(jax.make_jaxpr(grads(fn))(*args))
    val, got = grads(fn)(*args)
for g, o, r in zip((val, *got), (one_val, *one), (ref_val, *ref)):
    np.testing.assert_allclose(g, o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)
print("OK")
"""


@pytest.mark.parametrize(
    "kernel,shape",
    [
        ("flash", (4, 4, 2, 32, 16)),  # batch splits over the 4 devices
        ("flash", (2, 8, 4, 32, 16)),  # batch 2 does not: heads split
        ("flash", (1, 6, 2, 32, 16)),  # neither splits: replicated operands
        ("rmsnorm", (8, 16, 64)),  # rows split
        ("rmsnorm", (3, 64)),  # replicated
    ],
)
def test_kernel_grads_under_four_device_mesh(kernel, shape):
    """Under a 2x2 mesh each kernel runs per device in a shard_map; its
    value and gradients (replicated operands' included) equal the
    one-device kernel's and the oracle's."""
    from helpers import run_multidevice

    out = run_multidevice(
        _MESH_GRAD_CHILD.format(kernel=kernel, shape=shape), devices=4
    )
    assert "OK" in out
