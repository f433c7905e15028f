"""Training driver: the program's jitted training step, fed as
``repro.launch.train.main`` feeds it.

Set-up builds one object, the compiled step with its state (weights made
on the devices from the seed by the program's jitted initializer, AdamW
state, the in/out shardings and donation of ``launch/train.py``), and
drives it through its first steps on rows that all differ. Those steps
compile and warm the program and give the check its readings: each step's
loss, the first clipped gradient (from AdamW's first moment after one
step) and the parameters' change over the steps. The window then goes on
with the same object, one host-made batch per step, keeping at most two
steps queued ahead of the device.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.steps import make_train_step
from repro.launch.train import init_sharded_params
from repro.optim import AdamWConfig, adamw_init, warmup_cosine
from repro.parallel.mesh_view import build_mesh_context
from repro.parallel.sharding import batch_pspecs, opt_state_pspecs, to_shardings

from bench import flops
from bench.check import checked, train_numbers
from bench.gen import SyntheticTokens, seed_key

__all__ = ["Job", "leaf_norms"]


def _leaf_name(path) -> str:
    return ".".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)


def leaf_norms(tree) -> dict:
    """Frobenius norm of each leaf of the program's parameter tree."""
    return {
        _leaf_name(path): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def reference_optimizer(ref, opt: dict):
    """The reference module's AdamW with the configuration's schedule."""
    return ref.AdamW(peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
                     total_steps=opt["total_steps"])


class Job:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, devices
        traffic, opt = cell.traffic, cell.config["optimizer"]
        self.cfg = cfg = ModelConfig(**cell.model)
        mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
        self.ctx = ctx = build_mesh_context(mesh, cfg)
        self.batch, self.seq = traffic["batch"], traffic["seq_len"]
        shape = ShapeSpec("bench", self.seq, self.batch, "train", traffic["microbatches"])
        self.b1 = AdamWConfig.b1
        opt_cfg = AdamWConfig(
            learning_rate=warmup_cosine(opt["peak_lr"], opt["warmup_steps"], opt["total_steps"])
        )
        step_fn = make_train_step(cfg, ctx, shape, opt_cfg)
        self.data = SyntheticTokens(cfg.vocab_size, self.seq, self.batch, seed,
                                    traffic["mean_doc_len"])
        self.key = seed_key(seed)
        with jax.set_mesh(ctx.mesh):
            self.params, p_sh = init_sharded_params(cfg, ctx, self.key)
            o_sh = to_shardings(ctx, opt_state_pspecs(cfg, ctx, self.params))
            self.opt_state = jax.jit(adamw_init, out_shardings=o_sh)(self.params)
            b_specs = batch_pspecs(cfg, ctx, shape)
            b_sh = to_shardings(ctx, {k: b_specs[k] for k in ("tokens", "labels")})
            self.step = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, NamedSharding(ctx.mesh, P())),
                donate_argnums=(0, 1),
            )
        self.index = 0
        self.check_setup_s = 0.0
        # The first steps: compile, warm up, and record the check's readings.
        norms = jax.jit(leaf_norms)
        delta = jax.jit(lambda p, q: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), p, q)))
        self.losses, self.grad_norms = [], None
        for _ in range(traffic["check_steps"]):
            metrics = self._step()
            self.losses.append(float(metrics["loss"]))
            if self.grad_norms is None:
                c0 = time.perf_counter()
                with jax.set_mesh(ctx.mesh):
                    m = norms(self.opt_state["m"])
                self.grad_norms = {k: float(v) / (1 - self.b1) for k, v in m.items()}
                self.check_setup_s += time.perf_counter() - c0
        c0 = time.perf_counter()
        with jax.set_mesh(ctx.mesh):
            p0, _ = init_sharded_params(cfg, ctx, self.key)
            self.delta_norms = {k: float(v) for k, v in delta(self.params, p0).items()}
        del p0
        self.check_setup_s += time.perf_counter() - c0
        jax.block_until_ready(self.params)

    def _step(self):
        with jax.profiler.TraceAnnotation("bench.input"):
            batch = {k: jnp.asarray(v) for k, v in self.data.batch(self.index).items()}
        with jax.set_mesh(self.ctx.mesh), jax.profiler.TraceAnnotation("bench.step"):
            self.params, self.opt_state, metrics = self.step(self.params, self.opt_state, batch)
        self.index += 1
        return metrics

    def window(self, seconds: float) -> dict:
        losses = []
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            losses.append(self._step()["loss"])
            if len(losses) > 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    losses[-3].block_until_ready()
        jax.block_until_ready((self.params, self.opt_state))
        elapsed = time.perf_counter() - t0
        steps = len(losses)
        values = np.array([float(x) for x in losses])
        tokens = steps * self.batch * self.seq
        return {
            "attempted": steps,
            "failed": int((~np.isfinite(values)).sum()),
            "metrics": {"train_tokens_per_s": tokens / elapsed},
            "steps": steps,
            "step_module": f"jit_{self.step.__name__}",
            "step_flops": flops.train_step_flops(self.cell.model, self.batch, self.seq),
            "batch": self.batch,
            "seq": self.seq,
        }

    def free(self) -> None:
        del self.params, self.opt_state, self.step

    def reference(self, precision: str = "f32", batch_fault: bool = False) -> dict:
        ref = self.cell.reference
        arch = ref.Arch.from_model({**self.cell.model, **self.cell.config["assumed"]}, self.ctx.ep)
        opt = reference_optimizer(ref, self.cell.config["optimizer"])
        model = ref.TrainReference(arch, opt, np.array(self.devices), precision, batch_fault)
        batches = [self.data.batch(i) for i in range(len(self.losses))]
        return model.run(self.key, batches)

    def program(self) -> dict:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "delta_norms": self.delta_norms}

    def check(self) -> list[dict]:
        numbers = train_numbers(self.program(), self.reference())
        return checked(numbers, self.cell.limits)
