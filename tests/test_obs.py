"""Layer scopes in the compiled programs, host spans and the compile counter
(``repro.obs``)."""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from helpers import run_multidevice
from repro import obs
from repro.compat import make_mesh
from repro.configs import get_config
from repro.launch.steps import make_decode_step
from repro.launch.train import init_sharded_params
from repro.models import init_cache
from repro.parallel.mesh_view import build_mesh_context

INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*?\s([a-z][a-z0-9\-]*)\(")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def instructions(hlo_text: str):
    """(opcode, op_name or None) of every instruction of an HLO dump."""
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if m:
            name = OP_NAME.search(line)
            yield m.group(1), name.group(1) if name else None


def components(op_name: str) -> set[str]:
    """The scope names on an op_name's path, autodiff wrappers removed:
    ``transpose(jvp(moe))`` counts as ``moe``."""
    out = set()
    for part in re.split(r"[/;]", op_name):
        while (m := re.fullmatch(r"[\w\-]+\((.*)\)", part)):
            part = m.group(1)
        out.add(part)
    return out


def test_components_unwrap_autodiff():
    assert {"moe", "experts"} <= components("jit(f)/transpose(jvp(moe))/jvp(experts)/dot_general")
    assert "moe" not in components("jit(f)/moe_x/dot_general")


def test_decode_step_products_are_scoped():
    """Every matrix product of the compiled decode step lies under a layer."""
    cfg = get_config("mixtral-8x7b").reduced()
    ctx = build_mesh_context(make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1]), cfg)
    with jax.set_mesh(ctx.mesh):
        params, _ = init_sharded_params(cfg, ctx, jax.random.PRNGKey(0))
        cache = init_cache(cfg, 4, 16)
        step = jax.jit(make_decode_step(cfg, ctx), donate_argnums=(1,))
        text = step.lower(params, cache, {"tokens": jnp.zeros((4, 1), jnp.int32)},
                          jnp.int32(3)).compile().as_text()
    products = [n for op, n in instructions(text) if op in ("dot", "convolution")]
    assert len(products) >= 8, products
    layers = {obs.ATTN, obs.MOE, obs.HEAD}
    unscoped = [n for n in products if n is None or not components(n) & layers]
    assert not unscoped, unscoped
    found = set().union(*(components(n) for n in products))
    assert {obs.ATTN, obs.MOE, obs.HEAD, obs.ROUTER, obs.EXPERTS} <= found
    # The decode MoE's gate-weighted sum lies inside the expert FFN
    # (``routed_expert_ffn``); ``combine`` builds the per-token gates.
    assert any(n and obs.COMBINE in components(n) for _, n in instructions(text))


TRAIN_HLO = """
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import make_mesh
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.launch.steps import make_train_step
from repro.launch.train import init_sharded_params
from repro.optim import adamw_init
from repro.parallel.mesh_view import build_mesh_context
from repro.parallel.sharding import batch_pspecs, opt_state_pspecs, to_shardings

# Eight experts over four devices, two per device as Mixtral at ep = 4.
cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(), num_experts=8,
                          dispatch_mode="{mode}", num_rails=2, dispatch_chunks=2)
ctx = build_mesh_context(make_mesh((1, 4), ("data", "model")), cfg)
assert ctx.ep == 4, ctx.ep
shape = ShapeSpec("t", 32, 4, "train", 1)
with jax.set_mesh(ctx.mesh):
    params, p_sh = init_sharded_params(cfg, ctx, jax.random.PRNGKey(0))
    o_sh = to_shardings(ctx, opt_state_pspecs(cfg, ctx, params))
    opt = jax.jit(adamw_init, out_shardings=o_sh)(params)
    b_specs = batch_pspecs(cfg, ctx, shape)
    b_sh = to_shardings(ctx, {{k: b_specs[k] for k in ("tokens", "labels")}})
    batch = {{k: jnp.zeros((4, 32), jnp.int32) for k in ("tokens", "labels")}}
    step = jax.jit(make_train_step(cfg, ctx, shape), in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, NamedSharding(ctx.mesh, P())))
    print(step.lower(params, opt, batch).compile().as_text())
"""


@pytest.mark.parametrize("mode", ["dense", "rails", "spray", "ring"])
def test_train_step_dispatch_scopes(mode):
    """In an expert-parallel train step on four devices (compiled, not run):
    every all-to-all and collective-permute the program issues lies under
    ``a2a``, and every expert product under ``experts``."""
    text = run_multidevice(TRAIN_HLO.format(mode=mode), devices=4)
    instrs = list(instructions(text))
    # The program's own collectives carry the primitive's name last; the
    # partitioner's reshards are named after the op they reshard.
    issued = [n for op, n in instrs
              if op in ("all-to-all", "collective-permute", "collective-permute-start")
              and n and n.rsplit("/", 1)[-1] in ("all_to_all", "ppermute")]
    assert issued
    assert all(obs.A2A in components(n) and obs.MOE in components(n) for n in issued), issued
    moe_products = [n for op, n in instrs if op in ("dot", "convolution")
                    and n and obs.MOE in components(n)]
    experts = [n for n in moe_products if obs.EXPERTS in components(n)]
    assert len(experts) >= 3 * 3, experts  # forward, its recomputation, backward
    assert all(components(n) & {obs.ROUTER, obs.EXPERTS} for n in moe_products), moe_products


def test_span_totals():
    before = obs.spans().get("test.span", obs.Total())
    for _ in range(2):
        with obs.span("test.span") as this:
            time.sleep(0.01)
        assert this.count == 1 and this.seconds >= 0.01
    after = obs.spans()["test.span"]
    assert after.count - before.count == 2
    assert after.seconds - before.seconds >= 0.02


def test_compile_counter_sees_a_fresh_jit():
    def obs_probe(x):
        return x * 3 + 1

    before = obs.compiles().get("jit(obs_probe)", obs.Total())
    jax.jit(obs_probe)(jnp.arange(5.0)).block_until_ready()
    after = obs.compiles()["jit(obs_probe)"]
    assert after.count == before.count + 1 and after.seconds > before.seconds


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_key_includes_metadata(env_dir, tmp_path, monkeypatch):
    from repro import compile_cache

    keys = ("jax_compilation_cache_include_metadata_in_key", "jax_compilation_cache_dir")
    saved = {k: getattr(jax.config, k) for k in keys}
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
        used = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
        assert used == (str(tmp_path) if env_dir else str(compile_cache.CACHE_DIR))
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
