"""Plain reference of a Mixtral decoder, in ``jax.numpy`` at float32.

Written from the published architecture (arXiv:2401.04088, the Hugging
Face ``MixtralForCausalLM`` config) and from the semantics that a
configuration file states, and from nothing of the program under test:

* pre-norm decoder blocks: RMSNorm, grouped-query attention with
  rotate-half RoPE and a causal mask, RMSNorm, a sparse MoE of SwiGLU
  experts with softmax top-k routing whose weights are renormalised over
  the k chosen experts; a final RMSNorm and an untied output head;
* the stated departure from dropless routing: where a configuration gives
  ``capacity_factor``, routed tokens are counted in groups of
  ``group_tokens`` consecutive tokens (token-major, then by rank among the
  k choices) and each expert keeps its first ``int(group * k * cf / E)``;
  batches of fewer than ``8 * ep`` tokens are routed without a limit;
* the router's load-balancing term, ``E * sum(frac * mean_prob) / k`` per
  expert-parallel shard of consecutive tokens, averaged over the shards and
  summed over the layers, added to the mean next-token NLL;
* AdamW with global-norm clipping and a linear warm-up then cosine decay,
  with bfloat16 parameters and float32 moments.

Weights are made from a PRNG key by the recipe the configuration states
(normal, scaled by ``fan_in ** -0.5``, stored in bfloat16; the router in
float32; norms at one), one layer at a time, so that the reference can be
run in blocks that fit beside nothing else.

``precision`` selects the arithmetic of every matrix product: ``"f32"``
(float32 at ``HIGHEST``, the reference) or ``"fp8"`` (the control, the
nearest precision below the bfloat16 that the configurations state:
operands rounded to float8 e4m3 and, in the backward pass, cotangents to
float8 e5m2, each tensor with one scale; products accumulate in float32).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "Arch",
    "init_layer",
    "init_outer",
    "init_params",
    "forward_logits",
    "TrainReference",
]

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    """What the reference needs to know of a configuration."""

    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_d_ff: int
    num_experts: int
    experts_per_token: int
    vocab_size: int
    num_layers: int
    rope_theta: float
    rms_eps: float
    router_aux_coef: float
    capacity_factor: Optional[float]
    group_tokens: int
    ep: int

    @classmethod
    def from_model(cls, model: dict, ep: int) -> "Arch":
        return cls(
            d_model=model["d_model"],
            num_heads=model["num_heads"],
            num_kv_heads=model["num_kv_heads"],
            head_dim=model.get("head_dim") or model["d_model"] // model["num_heads"],
            moe_d_ff=model["moe_d_ff"],
            num_experts=model["num_experts"],
            experts_per_token=model["experts_per_token"],
            vocab_size=model["vocab_size"],
            num_layers=model["num_layers"],
            rope_theta=model["rope_theta"],
            rms_eps=model["rms_eps"],
            router_aux_coef=model["router_aux_coef"],
            capacity_factor=model.get("capacity_factor"),
            group_tokens=model.get("group_tokens", 1024),
            ep=ep,
        )


# ---------------------------------------------------------------------------
# Weights from the key
# ---------------------------------------------------------------------------


def _normal(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_outer(a: Arch, key) -> dict:
    """Embedding, output head and final norm."""
    keys = jax.random.split(key, 8)
    d, v = a.d_model, a.vocab_size
    return {
        "embed": _normal(keys[0], (v, d), d**-0.5),
        "lm_head": _normal(keys[1], (v, d), d**-0.5),
        "final_norm": jnp.ones((d,), jnp.bfloat16),
    }


def init_layer(a: Arch, key, layer: int) -> dict:
    """Layer ``layer``'s weights: its attention key is the ``layer``-th of
    ``split(keys[2], L)``, its MoE key the ``layer``-th of ``split(keys[3], L)``."""
    keys = jax.random.split(key, 8)
    d, h, hkv, hd = a.d_model, a.num_heads, a.num_kv_heads, a.head_dim
    e, f = a.num_experts, a.moe_d_ff
    ka = jax.random.split(jax.random.split(keys[2], a.num_layers)[layer], 4)
    km = jax.random.split(jax.random.split(keys[3], a.num_layers)[layer], 4)
    return {
        "wq": _normal(ka[0], (d, h * hd), d**-0.5),
        "wk": _normal(ka[1], (d, hkv * hd), d**-0.5),
        "wv": _normal(ka[2], (d, hkv * hd), d**-0.5),
        "wo": _normal(ka[3], (h * hd, d), (h * hd) ** -0.5),
        "router": _normal(km[0], (d, e), d**-0.5, jnp.float32),
        "w_gate": _normal(km[1], (e, d, f), d**-0.5),
        "w_up": _normal(km[2], (e, d, f), d**-0.5),
        "w_down": _normal(km[3], (e, f, d), f**-0.5),
        "ln1": jnp.ones((d,), jnp.bfloat16),
        "ln2": jnp.ones((d,), jnp.bfloat16),
    }


def init_params(a: Arch, key) -> dict:
    return {**init_outer(a, key),
            "layers": [init_layer(a, key, i) for i in range(a.num_layers)]}


#: Per-leaf names as the program's parameter tree stacks them over layers.
LAYER_LEAVES = {
    "wq": "blocks.attn.wq", "wk": "blocks.attn.wk", "wv": "blocks.attn.wv",
    "wo": "blocks.attn.wo", "router": "blocks.moe.router",
    "w_gate": "blocks.moe.w_gate", "w_up": "blocks.moe.w_up",
    "w_down": "blocks.moe.w_down", "ln1": "blocks.ln1", "ln2": "blocks.ln2",
}


def leaf_norms(tree: dict) -> dict:
    """Frobenius norm of each stacked leaf (layers pooled), in float32."""
    sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))
    out = {k: jnp.sqrt(sq(tree[k])) for k in ("embed", "lm_head", "final_norm")}
    for k, name in LAYER_LEAVES.items():
        out[name] = jnp.sqrt(sum(sq(lp[k]) for lp in tree["layers"]))
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _quantize(x, dtype):
    """Round to a float8 type with one scale per tensor (its largest
    magnitude mapped to the type's largest finite value)."""
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(eq, x, y):
    return _einsum_fp8_fwd(eq, x, y)[0]


def _einsum_fp8_fwd(eq, x, y):
    xq, yq = _quantize(x, jnp.float8_e4m3fn), _quantize(y, jnp.float8_e4m3fn)
    return jnp.einsum(eq, xq, yq, precision=HIGHEST), (xq, yq)


def _einsum_fp8_bwd(eq, res, g):
    """Gradients from the float8 operands and the cotangent in float8 e5m2."""
    _, vjp = jax.vjp(lambda a, b: jnp.einsum(eq, a, b, precision=HIGHEST), *res)
    return vjp(_quantize(g, jnp.float8_e5m2))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def _mm(eq: str, x, y, precision: str):
    x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    if precision == "fp8":
        return _einsum_fp8(eq, x, y)
    return jnp.einsum(eq, x, y, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, T, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(a: Arch, lp, x, precision):
    b, t, _ = x.shape
    h, hkv, hd = a.num_heads, a.num_kv_heads, a.head_dim
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    q = _rope(_mm("btd,dk->btk", x, lp["wq"], precision).reshape(b, t, h, hd), pos, a.rope_theta)
    k = _rope(_mm("btd,dk->btk", x, lp["wk"], precision).reshape(b, t, hkv, hd), pos, a.rope_theta)
    v = _mm("btd,dk->btk", x, lp["wv"], precision).reshape(b, t, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q * hd**-0.5, k, precision)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision)
    return _mm("btk,kd->btd", o.reshape(b, t, h * hd), lp["wo"], precision)


def _route(a: Arch, lp, x2, n_shards: int):
    """Gates ``(N, E)`` of the kept routes, and the load-balancing term."""
    n = x2.shape[0]
    e, k = a.num_experts, a.experts_per_token
    probs = jax.nn.softmax(jnp.einsum("nd,de->ne", x2, lp["router"], precision=HIGHEST), -1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (N, k, E)
    keep = jnp.ones((n, k), bool)
    if a.capacity_factor is not None and n >= 8 * a.ep and n % a.ep == 0:
        rows = n // a.ep
        tg = min(a.group_tokens, rows)
        while rows % tg:
            tg -= 1
        cap = max(1, int(tg * k * a.capacity_factor / e))
        rank = jnp.cumsum(onehot.reshape(n // tg, tg * k, e), axis=1) - 1.0
        rank = jnp.sum(rank * onehot.reshape(n // tg, tg * k, e), -1).reshape(n, k)
        keep = rank < cap
    gates = jnp.einsum("nk,nke->ne", w * keep, onehot)
    per = onehot.reshape(n_shards, n // n_shards, k, e)
    frac = jnp.mean(jnp.sum(per, 2), 1)
    mean_prob = jnp.mean(probs.reshape(n_shards, n // n_shards, e), 1)
    aux = jnp.mean(e * jnp.sum(frac * mean_prob, -1) / k)
    return gates, aux


def _experts(x2, gates, wg, wu, wd, precision):
    """Every expert on every token of ``x2``, weighted by ``gates (N, E)``."""
    g = _mm("nd,edf->nef", x2, wg, precision)
    u = _mm("nd,edf->nef", x2, wu, precision)
    y = _mm("nef,efd->ned", jax.nn.silu(g) * u, wd, precision)
    return jnp.einsum("ned,ne->nd", y, gates, precision=HIGHEST)


def _moe(a: Arch, lp, x, precision, token_chunks=1, replicate=None):
    """The sparse MoE, computed densely: every expert runs on every token
    and the gates zero what routing did not keep. ``token_chunks`` splits
    the tokens into blocks, each recomputed in the backward pass."""
    b, t, d = x.shape
    n = b * t
    x2 = x.reshape(n, d)
    n_shards = a.ep if n % a.ep == 0 else 1
    gates, aux = _route(a, lp, x2, n_shards)
    if replicate is not None:
        x2, gates = replicate(x2), replicate(gates)
    block = jax.checkpoint(_experts, static_argnums=(5,))
    c = n // token_chunks
    outs = [block(x2[i * c:(i + 1) * c], gates[i * c:(i + 1) * c],
                  lp["w_gate"], lp["w_up"], lp["w_down"], precision)
            for i in range(token_chunks)]
    return jnp.concatenate(outs).reshape(b, t, d), aux


def layer_forward(a: Arch, lp, x, precision: str, token_chunks=1, replicate=None):
    """One decoder block on ``x: (B, T, D)`` float32; returns (x, aux)."""
    x = x + _attention(a, lp, _rmsnorm(x, lp["ln1"], a.rms_eps), precision)
    h, aux = _moe(a, lp, _rmsnorm(x, lp["ln2"], a.rms_eps), precision, token_chunks, replicate)
    return x + h, aux


def head_logits(a: Arch, outer, x, precision: str):
    x = _rmsnorm(x, outer["final_norm"], a.rms_eps)
    return _mm("btd,vd->btv", x, outer["lm_head"], precision)


def forward_logits(a: Arch, key, tokens, precision: str = "f32"):
    """Logits ``(B, T, V)`` of ``tokens`` under the weights made from
    ``key``, one layer's weights on the device at a time."""
    outer = jax.jit(lambda k: init_outer(a, k))(key)
    make_layer = jax.jit(lambda k, i: init_layer(a, k, i), static_argnums=1)
    x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(outer["embed"], tokens)
    step = jax.jit(lambda lp, x: layer_forward(a, lp, x, precision)[0])
    for i in range(a.num_layers):
        lp = make_layer(key, i)
        x = step(lp, x)
        del lp
    return jax.jit(lambda o, x: head_logits(a, o, x, precision))(outer, x)


# ---------------------------------------------------------------------------
# Training: loss, gradients and AdamW over a few steps
# ---------------------------------------------------------------------------


def _loss(a: Arch, params, tokens, labels, precision, token_chunks, replicate):
    x = params["embed"][tokens].astype(jnp.float32)
    aux_total = 0.0
    block = jax.checkpoint(
        lambda lp, x: layer_forward(a, lp, x, precision, token_chunks, replicate)
    )
    for lp in params["layers"]:
        x, aux = block(lp, x)
        aux_total = aux_total + aux
    logits = head_logits(a, params, x, precision)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold) + a.router_aux_coef * aux_total


def warmup_cosine(count, peak, warmup, total, floor=0.1):
    step = count.astype(jnp.float32)
    warm = peak * step / max(warmup, 1)
    progress = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = floor * peak + (1 - floor) * peak * 0.5 * (1 + jnp.cos(jnp.pi * progress))
    return jnp.where(step < warmup, warm, cos)


@dataclasses.dataclass(frozen=True)
class AdamW:
    peak_lr: float
    warmup_steps: int
    total_steps: int
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


class TrainReference:
    """The reference's own training state on a 1-D mesh of ``devices``:
    experts split over the devices, vocabulary rows split, projections
    split on their output (``wq/wk/wv``) or input (``wo``) side, rows of
    the batch split; everything else replicated.

    A step is two programs: the gradients, then the AdamW update. While
    the gradients are computed, AdamW's moments wait in the host's memory,
    one plain copy per device shard, so that the float32 activations, the
    float32 gradients and the moments are never on a device together."""

    def __init__(self, a: Arch, opt: AdamW, devices, precision: str = "f32",
                 batch_fault: bool = False, token_chunks: int = 4):
        self.a, self.opt, self.precision = a, opt, precision
        self.token_chunks = token_chunks
        self.batch_fault = batch_fault
        self.mesh = jax.sharding.Mesh(devices, ("x",))
        n = len(devices)
        sh = lambda *spec: NamedSharding(self.mesh, P(*spec))
        e_ax = "x" if a.num_experts % n == 0 else None
        v_ax = "x" if a.vocab_size % n == 0 else None
        layer = {
            "wq": sh(None, "x"), "wk": sh(None, "x"), "wv": sh(None, "x"),
            "wo": sh("x", None), "router": sh(), "w_gate": sh(e_ax, None, None),
            "w_up": sh(e_ax, None, None), "w_down": sh(e_ax, None, None),
            "ln1": sh(), "ln2": sh(),
        }
        self.param_sh = {
            "embed": sh(v_ax, None), "lm_head": sh(v_ax, None), "final_norm": sh(),
            "layers": [layer] * a.num_layers,
        }
        self.batch_sh = sh("x", None)
        self._replicated = sh()
        self._init = jax.jit(lambda k: init_params(a, k), out_shardings=self.param_sh)
        zeros = lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        self._zeros = jax.jit(zeros, out_shardings=self.param_sh)
        self._grads = jax.jit(
            self._grads_fn, in_shardings=(self.param_sh, self.batch_sh, self.batch_sh),
            out_shardings=(None, self.param_sh, None),
        )
        self._update = jax.jit(
            self._update_fn,
            in_shardings=(self.param_sh, self.param_sh, self.param_sh, None, self.param_sh),
            out_shardings=(self.param_sh, self.param_sh, self.param_sh, None),
            donate_argnums=(0, 1, 2, 4),
        )
        self._delta = jax.jit(
            lambda p, q: leaf_norms(jax.tree.map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), p, q))
        )

    def _grads_fn(self, params, tokens, labels):
        """Loss, the clipped gradient, and its per-leaf norms."""
        replicate = lambda w: jax.lax.with_sharding_constraint(w, self._replicated)
        if self.batch_fault:
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        loss, grads = jax.value_and_grad(_loss, argnums=1)(
            self.a, params, tokens, labels, self.precision, self.token_chunks, replicate)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, self.opt.grad_clip / jnp.maximum(gnorm, 1e-12))
        clipped = jax.tree.map(lambda g: g * scale, grads)
        return loss, clipped, leaf_norms(clipped)

    def _update_fn(self, params, m, v, count, g):
        o = self.opt
        count = count + 1
        lr = warmup_cosine(count, o.peak_lr, o.warmup_steps, o.total_steps)
        m = jax.tree.map(lambda m, g: o.b1 * m + (1 - o.b1) * g, m, g)
        v = jax.tree.map(lambda v, g: o.b2 * v + (1 - o.b2) * g * g, v, g)
        c1, c2 = 1 - o.b1 ** count, 1 - o.b2 ** count

        def update(p, m, v):
            pf = p.astype(jnp.float32)
            step = (m / c1) / (jnp.sqrt(v / c2) + o.eps) + o.weight_decay * pf
            return (pf - lr * step).astype(p.dtype)

        return jax.tree.map(update, params, m, v), m, v, count

    @staticmethod
    def _park(tree):
        """Each leaf as its shards copied to the host: (shape, sharding,
        [(device, numpy array), ...]). Bit for bit what the devices held."""
        leaves, treedef = jax.tree.flatten(tree)
        shards = [[(s.device, s.data) for s in x.addressable_shards] for x in leaves]
        for leaf in shards:
            for _, a in leaf:
                a.copy_to_host_async()
        return treedef, [(x.shape, x.sharding, [(d, np.asarray(a)) for d, a in leaf])
                         for x, leaf in zip(leaves, shards)]

    @staticmethod
    def _unpark(parked):
        treedef, leaves = parked
        return jax.tree.unflatten(treedef, [
            jax.make_array_from_single_device_arrays(
                shape, sharding, [jax.device_put(h, d) for d, h in shards])
            for shape, sharding, shards in leaves])

    def run(self, key, batches: list[dict]) -> dict:
        """Losses of each step, per-leaf norms of the first clipped
        gradient, and per-leaf norms of the parameters' change."""
        params = self._init(key)
        m = self._park(self._zeros(params))
        v = self._park(self._zeros(params))
        count = jnp.int32(0)
        losses, first_grad = [], None
        for b in batches:
            loss, g, gnorms = self._grads(params, b["tokens"], b["labels"])
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {k: float(x) for k, x in gnorms.items()}
            m, v = self._unpark(m), self._unpark(v)
            params, m, v, count = self._update(params, m, v, count, g)
            del g
            m, v = self._park(m), self._park(v)
        del m, v
        delta = {k: float(x) for k, x in self._delta(params, self._init(key)).items()}
        return {"losses": losses, "grad_norms": first_grad, "delta_norms": delta}
