#!/usr/bin/env python3
"""Compile every program a cell's run drives for a described TPU v5e, with
no chip attached, and print what ``memory_analysis`` gives per device.

    JAX_PLATFORMS=cpu python bench/aot.py [--workload <cell>]

Each cell's timed program is compiled at the cell's own sizes with the
Pallas kernels it runs on the chip, and so is its reference at the same
sizes: the training step of the reference, or one decoder layer of it.
Nothing runs; a compile that passes here is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "generated_code_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys}
    out["peak_estimate_bytes"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                                  + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    return out


def _abstract(tree, shardings):
    import jax

    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                        tree, shardings)


def lower_train_step(cell, devices):
    """The train driver's training step (``bench/drivers/train.py``), with
    its shardings and donation, lowered from shapes alone; and its mesh
    context."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.configs.base import ModelConfig, ShapeSpec
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.optim import AdamWConfig, adamw_init, warmup_cosine
    from repro.parallel.mesh_view import build_mesh_context
    from repro.parallel.sharding import (batch_pspecs, opt_state_pspecs, param_shardings,
                                         to_shardings)

    t, o = cell.traffic, cell.config["optimizer"]
    cfg = ModelConfig(**cell.model)
    ctx = build_mesh_context(make_mesh((1, len(devices)), ("data", "model"), devices=devices), cfg)
    shape = ShapeSpec("bench", t["seq_len"], t["batch"], "train", t["microbatches"])
    step = make_train_step(cfg, ctx, shape, AdamWConfig(
        learning_rate=warmup_cosine(o["peak_lr"], o["warmup_steps"], o["total_steps"])))
    with jax.set_mesh(ctx.mesh):
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        p_sh = param_shardings(cfg, ctx, params)
        o_sh = to_shardings(ctx, opt_state_pspecs(cfg, ctx, params))
        opt = jax.eval_shape(adamw_init, params)
        b_specs = batch_pspecs(cfg, ctx, shape)
        b_sh = to_shardings(ctx, {k: b_specs[k] for k in ("tokens", "labels")})
        batch = {k: jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), jnp.int32)
                 for k in ("tokens", "labels")}
        lowered = jax.jit(
            step, in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, NamedSharding(ctx.mesh, P())), donate_argnums=(0, 1),
        ).lower(_abstract(params, p_sh), _abstract(opt, o_sh), batch)
    return lowered, ctx


def train(cell, devices) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = cell.reference
    t, o = cell.traffic, cell.config["optimizer"]
    lowered, ctx = lower_train_step(cell, devices)
    compiled = lowered.compile()
    out = {"program_train_step": _bytes(compiled)}
    out["program_has_pallas"] = "tpu_custom_call" in compiled.as_text()

    arch = ref.Arch.from_model({**cell.model, **cell.config["assumed"]}, ctx.ep)
    rmodel = ref.TrainReference(arch, ref.AdamW(o["peak_lr"], o["warmup_steps"], o["total_steps"]),
                                np.array(devices))
    rp = jax.eval_shape(lambda: ref.init_params(arch, jax.random.PRNGKey(0)))
    f32 = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), tree)
    tok = jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), jnp.int32, sharding=rmodel.batch_sh)
    compiled = rmodel._grads.lower(_abstract(rp, rmodel.param_sh), tok, tok).compile()
    out["reference_grads"] = _bytes(compiled)
    state = _abstract(f32(rp), rmodel.param_sh)
    compiled = rmodel._update.lower(_abstract(rp, rmodel.param_sh), state, state,
                                    jax.ShapeDtypeStruct((), jnp.int32), state).compile()
    out["reference_update"] = _bytes(compiled)
    return out


def serve(cell, devices) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.compat import make_mesh
    from repro.configs.base import ModelConfig
    from repro.launch.steps import make_decode_step
    from repro.models import init_cache, init_params
    from repro.parallel.mesh_view import build_mesh_context
    from repro.parallel.sharding import param_shardings

    ref = cell.reference
    t = cell.traffic
    cfg = ModelConfig(**cell.model)
    ctx = build_mesh_context(make_mesh((1, 1), ("data", "model"), devices=devices[:1]), cfg)
    one = jax.sharding.SingleDeviceSharding(devices[0])
    out = {}
    with jax.set_mesh(ctx.mesh):
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        p_sh = param_shardings(cfg, ctx, params)
        cache = jax.eval_shape(lambda: init_cache(cfg, t["batch"], t["prompt_len"] + t["gen_len"]))
        cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), cache)
        tokens = {"tokens": jax.ShapeDtypeStruct((t["batch"], 1), jnp.int32, sharding=one)}
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        compiled = jax.jit(make_decode_step(cfg, ctx, return_counts=True),
                           donate_argnums=(1,)).lower(
            _abstract(params, p_sh), cache, tokens, pos).compile()
    out["program_decode_step"] = _bytes(compiled)
    arch = ref.Arch.from_model({**cell.model, **cell.config["assumed"]}, 1)
    lp = jax.eval_shape(lambda: ref.init_layer(arch, jax.random.PRNGKey(0), 0))
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), lp)
    n = t["check_requests"]
    x = jax.ShapeDtypeStruct((n, t["prompt_len"] + t["gen_len"] - 1, arch.d_model), jnp.float32,
                             sharding=one)
    compiled = jax.jit(lambda lp, x: ref.layer_forward(arch, lp, x, "f32")[0]).lower(lp, x).compile()
    out["reference_layer"] = _bytes(compiled)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import json

    import jax
    from jax.experimental import topologies

    from bench import run
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops.kernel_backend = lambda: "pallas"  # compile the kernels the chip runs
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in args.workload or [w["name"] for w in spec["workloads"]]:
        cell = run.load_cell(ROOT, name)
        fn = {"train": train, "serve": serve}[cell.traffic["driver"]]
        print(json.dumps({"workload": name, **fn(cell, list(topo.devices)[: cell.chips])}), flush=True)


if __name__ == "__main__":
    main()
