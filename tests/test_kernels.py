"""Pallas kernel sweeps: interpret-mode vs pure-jnp oracle (ref.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (
    KV_ROWS,
    QUERY_ROWS,
    SUBLANES,
    _kv_index_map,
    flash_attention_pallas,
    flash_tile_plan,
    flash_tiles,
)
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.moe_decode import routed_expert_ffn_pallas, routed_order
from repro.kernels.ref import (
    flash_attention_ref,
    grouped_matmul_ref,
    rmsnorm_ref,
    routed_expert_ffn_ref,
)
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
    # Shape-chosen tiles (``block_q``/``block_k`` None), a GQA group of 4:
    # 256 query positions (1024 rows) by 512 keys, masked tiles skipped.
    (2, 1024, 1024, 8, 2, 128, {"block_q": None, "block_k": None}),
    (1, 1024, 1024, 4, 1, 64, {"window": 100, "block_q": None, "block_k": None}),
    (1, 200, 700, 8, 2, 64, {"causal": False, "block_q": None, "block_k": None}),
    # A group of 3 (phi4-mini's 24 / 8 heads): 256 positions, 768 rows a step.
    (1, 600, 600, 6, 2, 64, {"block_q": None, "block_k": None}),
]


def _tiles(kw):
    """Split a case's kwargs into the kernel's tiles (32 unless the case
    names them) and the attention options."""
    kw = dict(kw)
    return {"block_q": kw.pop("block_q", 32), "block_k": kw.pop("block_k", 32)}, kw


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(case, dtype):
    b, t, s, h, hkv, hd, kw = case
    tiles, kw = _tiles(kw)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), dtype)
    got = flash_attention_pallas(q, k, v, interpret=True, **tiles, **kw)
    want = flash_attention_ref(q, k, v, block_k=48, **kw)
    oracle = _naive_attention(q, k, v, **kw)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), oracle.astype(jnp.float32), atol=tol, rtol=tol
    )
    np.testing.assert_allclose(
        want.astype(jnp.float32), oracle.astype(jnp.float32), atol=tol, rtol=tol
    )


TILE_PLAN_CASES = [
    # (t, s, block_q, block_k, causal, window, q_offset)
    (1024, 1024, 256, 512, True, None, 0),
    (1024, 1024, 128, 128, True, None, 0),
    (1000, 1000, 256, 512, True, None, 0),  # neither a multiple of its tile
    (1024, 1024, 256, 512, True, 100, 0),
    (700, 700, 64, 96, True, 150, 0),
    (96, 160, 32, 48, True, None, 64),  # a chunk after a prefix
    (40, 200, 16, 64, True, 70, 150),
    (200, 700, 64, 128, False, None, 0),  # cross attention
    (300, 300, 64, 128, False, 50, 0),
]


def _unmasked(t, s, causal, window, q_offset):
    q_pos = q_offset + np.arange(t)[:, None]
    k_pos = np.arange(s)[None, :]
    mask = np.ones((t, s), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


@pytest.mark.parametrize("case", TILE_PLAN_CASES)
def test_flash_tile_plan_counts_tiles_with_an_unmasked_pair(case):
    t, s, bq, bk, causal, window, q_offset = case
    mask = _unmasked(t, s, causal, window, q_offset)
    n_q, n_k = -(-t // bq), -(-s // bk)
    needed = np.array([[mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
                        for j in range(n_k)] for i in range(n_q)])
    plan = flash_tile_plan(t, s, bq, bk, causal, window, q_offset)
    assert plan.total == n_q * n_k
    assert plan.computed == needed.sum()
    for i in range(n_q):
        cols = np.flatnonzero(needed[i])
        assert (plan.first[i], plan.last[i]) == (cols[0], cols[-1])
        assert needed[i, plan.first[i]:plan.last[i] + 1].all()


@pytest.mark.parametrize("case", TILE_PLAN_CASES)
def test_skipped_kv_steps_repeat_a_needed_tile(case):
    """A step outside a query tile's needed KV tiles maps K/V to the
    nearest needed tile, the index of the step before or after it, so
    Pallas fetches nothing for it."""
    t, s, bq, bk, causal, window, q_offset = case
    plan = flash_tile_plan(t, s, bq, bk, causal, window, q_offset)
    n_k = -(-s // bk)
    index = _kv_index_map(t=t, seq_k=s, block_q=bq, block_k=bk, causal=causal,
                          window=window, q_offset=q_offset)
    for qi, (first, last) in enumerate(zip(plan.first, plan.last)):
        got = [int(index(3, qi, ki)[1]) for ki in range(n_k)]
        assert got == [min(max(ki, first), last) for ki in range(n_k)]
        assert got[last + 1:] == [last] * (n_k - 1 - last)
        assert int(index(3, qi, 0)[0]) == 3


def test_flash_tiles_at_the_training_shape():
    """Mixtral's training shape: 4 heads a group, 256 query positions
    (1024 rows) by 512 keys, 6 of 8 steps computed under the causal mask."""
    assert flash_tiles(1024, 1024, 4) == (256, 512)
    assert flash_tiles(1024, 1024, 4, 32, 32) == (32, 32)
    assert flash_tiles(50, 50, 1) == (64, 50)  # short: whole sequence, sublane multiple
    assert flash_tiles(4096, 4096, 3) == (256, 512)  # phi4-mini: a group of 3
    assert flash_tile_plan(1024, 1024, 256, 512)[2:] == (6, 8)


@pytest.mark.parametrize("rep", [1, 2, 3, 4, 5, 6, 8, 12, 64, 100])
def test_flash_tiles_query_block_is_a_power_of_two(rep):
    """The shape-chosen query block is a power of two of at least
    ``SUBLANES`` positions whose group fills at most ``QUERY_ROWS`` rows
    (or at least ``SUBLANES``), so it divides power-of-two sequences and
    meets the TPU's tiling of the block's second-minor dimension."""
    block_q, block_k = flash_tiles(8192, 8192, rep)
    assert block_q & (block_q - 1) == 0 and block_q >= SUBLANES
    assert rep * block_q <= max(QUERY_ROWS, rep * SUBLANES) < 2 * rep * block_q
    assert block_k == KV_ROWS


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


# -- the decode MoE kernel: only the routed experts' weights are read --------


def _routed_operands(n, e, k, d, f, dtype, routes=None, seed=0):
    """Tokens, gates and expert weights; ``routes`` (n, k) fixes the
    experts each token routes to, else they are drawn at random."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    wg, wu = (jnp.asarray(rng.normal(size=(e, d, f)) * d**-0.5, dtype) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * f**-0.5, dtype)
    if routes is None:
        routes = np.stack([rng.choice(e, size=k, replace=False) for _ in range(n)])
    routes = np.asarray(routes)
    w = rng.uniform(0.1, 1.0, size=routes.shape)
    gates = np.zeros((n, e))
    gates[np.arange(n)[:, None], routes] = w / w.sum(-1, keepdims=True)
    counts = np.bincount(routes.ravel(), minlength=e)
    return x, jnp.asarray(gates, dtype), wg, wu, wd, jnp.asarray(counts, jnp.int32)


ROUTED_CASES = {
    # name: (n, E, top_k, d, f, block_f, routes)
    "all-routed": (4, 8, 2, 32, 256, 128, [[0, 1], [2, 3], [4, 5], [6, 7]]),
    "one-routed": (4, 8, 1, 32, 256, 128, [[5], [5], [5], [5]]),
    "n1-e8-top2": (1, 8, 2, 64, 256, None, None),
    "n4-e8-top2": (4, 8, 2, 32, 256, 128, None),
    "n7-e8-top2": (7, 8, 2, 32, 128, None, None),
    "n4-e16-top4": (4, 16, 4, 32, 256, 128, None),
    "n7-e16-top4": (7, 16, 4, 64, 128, None, None),
}


@pytest.mark.parametrize("case", list(ROUTED_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_routed_expert_ffn_matches_oracle(case, dtype):
    n, e, k, d, f, block_f, routes = ROUTED_CASES[case]
    x, gates, wg, wu, wd, counts = _routed_operands(n, e, k, d, f, dtype, routes)
    order, n_active = routed_order(counts)
    got = routed_expert_ffn_pallas(x, gates, wg, wu, wd, order, n_active,
                                   block_f=block_f, interpret=True)
    want = routed_expert_ffn_ref(*(a.astype(jnp.float32) for a in (x, gates, wg, wu, wd)))
    assert got.shape == (n, d) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol, rtol=tol)


@pytest.mark.parametrize(
    "counts", [[2, 0, 0, 0, 1, 0, 1, 4], [0, 0, 0, 3, 0, 0, 0, 0], [1] * 8, [0, 5, 0, 0, 2, 0, 0, 0]]
)
def test_routed_order_puts_used_experts_first(counts):
    order, n_active = routed_order(jnp.asarray(counts, jnp.int32))
    order, n_active = np.asarray(order), int(n_active[0])
    used = [i for i, c in enumerate(counts) if c]
    assert n_active == np.count_nonzero(counts)
    assert order[:n_active].tolist() == used
    assert (order[n_active:] == used[-1]).all()


def test_routed_expert_ffn_vjp_matches_oracle_grad():
    x, gates, wg, wu, wd, counts = _routed_operands(3, 8, 2, 32, 256, jnp.float32)
    order, n_active = routed_order(counts)
    probe = jnp.asarray(np.random.default_rng(6).normal(size=x.shape), jnp.float32)

    def loss(ffn):
        return lambda *a: jnp.sum(ffn(*a) * probe)

    args = (x, gates, wg, wu, wd)
    got = jax.grad(loss(lambda *a: routed_expert_ffn_pallas(
        *a, order, n_active, block_f=128, interpret=True)), argnums=tuple(range(5)))(*args)
    want = jax.grad(loss(routed_expert_ffn_ref), argnums=tuple(range(5)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)


# -- custom VJPs: the kernels' backward passes against jax.grad of the oracles


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vjp_matches_oracle_grad(case):
    b, t, s, h, hkv, hd, kw = case
    tiles, kw = _tiles(kw)
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=shape), jnp.float32)
        for shape in ((b, t, h, hd), (b, s, hkv, hd), (b, s, hkv, hd))
    )
    probe = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * probe)

    got = jax.grad(
        loss(lambda q, k, v: flash_attention_pallas(q, k, v, interpret=True, **tiles, **kw)),
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

if {kernel!r} == "routed":
    from repro.kernels.moe_decode import routed_order
    from repro.kernels.ref import routed_expert_ffn_ref
    n, e, d, f = {shape}
    gates = jax.nn.softmax(arr(n, e), axis=-1) * (arr(n, e) > 0)
    # Weights at moe_init's scale.
    args = (arr(n, d), gates, arr(e, d, f) * d**-0.5, arr(e, d, f) * d**-0.5,
            arr(e, f, d) * f**-0.5)
    probe = arr(n, d)
    routes = routed_order(jnp.sum(gates > 0, axis=0))
    fn = lambda *a: ops.routed_expert_ffn(*a, *routes)
    oracle = routed_expert_ffn_ref
elif {kernel!r} == "flash":
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
        ("routed", (4, 8, 32, 128)),  # tokens split, weights replicated
        ("routed", (3, 8, 32, 128)),  # replicated
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
